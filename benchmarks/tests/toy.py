"""A cell cut to toy size for the CPU: same files, same functions."""

import time

from benchmarks import cells, harness


def toy_cell(name: str, repo: str = cells.REPO) -> dict:
    cell = cells.resolve_cell(name, repo)
    pipeline = cell["config"]["config"]["pipeline"]
    pipeline.update(width=256, registry_capacity=4096, ring_depth=2)
    cell["config"]["fleet"]["devices"] = 512
    cell["config"]["sample_devices"] = 32
    cell["traffic"].update(
        lines_per_payload=64, pool_payloads=8, rate_events_per_s=3000.0,
        pool_batches=4, prime_sends=2, clients=2, trace_seconds=1.0)
    return cell


def toy_run(name: str, seed: int = 3, seconds: float = 2.0,
            trace: bool = False, repo: str = cells.REPO):
    """(result, run record) of one toy run."""
    seen = {}
    result = harness.run_cell(
        toy_cell(name, repo), seed, seconds, trace, time.perf_counter(),
        require_tpu=False, on_run=lambda run: seen.update(run=run))
    return result, seen["run"]

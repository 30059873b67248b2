#!/usr/bin/env python3
"""The control: one run of a cell with a stated guarantee broken
underneath, which has to come out ``correct: false``.

    python3 benchmarks/control.py --workload <cell> --seed <n> --seconds <s> --fault <name>

This system runs no model and states no precision, so the control
breaks one guarantee the configuration states (``FAULTS``): the rest of
the run is the benchmark's own, at the cell's own size.  The
benchmark's runs never come here; ``tests/test_faults.py`` does, at toy
size, and a builder does on the chip.  Exits 0 when the comparison saw
the fault, 1 when it did not (or no chip was found), and prints the
comparisons that failed.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _half_of_every_batch(dep):
    """The send acknowledged in full, half of its rows handed on: an
    acknowledged event is not stored (durability)."""
    whole = dep.d.ingest_arrays

    def half(**cols):
        n = len(cols["device_id"]) // 2
        return whole(**{k: v[:n] for k, v in cols.items()})
    dep.d.ingest_arrays = half


def _a_state_answer_altered(dep):
    """A device's last value off by one where it is read back
    (last-known state is exact)."""
    state = dep.inst.device_state
    true = state.get_device_state_by_id

    def altered(handle):
        row = true(handle)
        row["last_values"] = [v + 1.0 for v in row["last_values"]]
        return row
    state.get_device_state_by_id = altered


def _another_threshold(dep):
    """The rules run against another threshold than the configuration
    states (alerts are exact): the control proper."""
    for rule in dep.config["rules"]["thresholds"]:
        rule["threshold"] = float(rule["threshold"]) - 5.0


# fault -> (planted before or after the deployment is populated, how,
#           the start of the name of a comparison that has to fail)
FAULTS = {
    "half-of-every-batch": ("after", _half_of_every_batch, "processed"),
    "state-answer-altered": ("after", _a_state_answer_altered, "state of "),
    "another-threshold": ("before", _another_threshold, "threshold_alerts"),
}


def broken(deployment, fault: str):
    """``deployment`` (the harness's class) with ``fault`` underneath;
    the reference keeps the configuration as its file states it."""
    when, plant, _ = FAULTS[fault]

    class Broken(deployment):
        def populate(self):
            stated = self.config
            if when == "before":
                self.config = {**stated, "rules": {
                    k: [dict(r) for r in v]
                    for k, v in stated["rules"].items()}}
                plant(self)
            super().populate()
            self.config = stated
            if when == "after":
                plant(self)

    return Broken


def failed_comparisons(result: dict) -> list:
    return [name for name, (got, limit) in result["compared"].items()
            if got != limit]


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fault", choices=sorted(FAULTS), required=True)
    p.add_argument("--no-chip", action="store_true",
                   help="do not look for the chip (the CPU, a toy cell)")
    args = p.parse_args()

    sys.path.insert(0, REPO)
    from benchmarks import cells, harness

    cell = cells.resolve_cell(args.workload)
    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
    harness.Deployment = broken(harness.Deployment, args.fault)
    result = harness.run_cell(
        cell, args.seed, args.seconds, False, T_PROCESS,
        require_tpu=not args.no_chip,
        log=lambda line: print(line, flush=True))
    if result is None:
        return 1
    failed = failed_comparisons(result)
    must = FAULTS[args.fault][2]
    seen = (result["correct"] is False
            and any(name.startswith(must) for name in failed))
    print(json.dumps({
        "control": args.fault, "workload": args.workload, "seed": args.seed,
        "correct": result["correct"], "seen": seen,
        "failed_comparisons": {n: result["compared"][n] for n in failed},
        "compared": len(result["compared"]),
        "attempted": result["attempted"], "failed": result["failed"]}),
        flush=True)
    return 0 if seen else 1


if __name__ == "__main__":
    sys.exit(main())

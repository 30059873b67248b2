"""Each cell's body at toy size on the CPU (the mesh cell on 4 virtual
devices), through the same files and functions as on the chip."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import cells
from benchmarks.tests.toy import toy_cell, toy_run

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_body_at_toy_size(name):
    result, run = toy_run(name, trace=False)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    # every number compared beside its limit, an exact one equal to it
    assert len(result["compared"]) >= 20
    for what, (got, limit) in result["compared"].items():
        assert got == limit, what
    assert result["attempted"] > 0
    cell = cells.resolve_cell(name)
    assert set(result["metrics"]) == {e["name"] for e, _ in
                                      cell["end_to_end"]}
    for doc in result["metrics"].values():
        assert doc["value"] > 0 and set(doc) == {"value", "unit"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert run.n_shards == cell["chips"]
    json.dumps(result)


def test_traced_run_reports_the_per_layer_metrics_it_can_read():
    result, run = toy_run(CELLS[0], trace=True)
    assert result["correct"] is True
    cell = cells.resolve_cell(CELLS[0])
    wanted = {e["name"] for e, _ in cell["per_layer"]}
    assert set(result["metrics"]) <= wanted
    # the CPU has no device plane: what comes from the trace is left out
    trace_fed = {e["name"] for e, _ in cell["per_layer"]
                 if e["source"] == "device_trace"}
    assert not trace_fed & set(result["metrics"])
    assert {"wire_plan_fill_share", "not_normal_share", "gen_late_p99_ms",
            "plan_latency_p50_ms", "watchdog_trips",
            "checkpoints_in_window"} <= set(result["metrics"])
    # no checkpoint in a 2 s window: nothing to measure the slice from
    assert "trace_since_checkpoint_s" not in result["metrics"]
    assert result["metrics"]["wire_plan_fill_share"]["value"] > 0
    assert "breakdown" not in result


def test_the_seed_changes_the_inputs_and_nothing_else():
    picks, shapes = [], []
    for seed in (1, 2):
        _, run = toy_run(CELLS[0], seed=seed, seconds=1.0)
        log, params = run.sends, run.traffic
        measured = np.nonzero(log.measured)[0]
        shapes.append((len(measured), int(log.n[measured].sum()),
                       params["rate_events_per_s"],
                       params["lines_per_payload"]))
        picks.append(run.delivery.delivered.sum())
    assert shapes[0] == shapes[1]

    cell = toy_cell(CELLS[0])
    kind = cells.load_module(cell["traffic"]["kind_file"])

    class Dep:
        config = cell["config"]
        tokens = [f"d-{i}" for i in range(4096)]
        handles = np.arange(4096, dtype=np.int32)

    params = dict(cell["traffic"], lines_per_payload=1024, pool_payloads=32)
    a, b, a2 = (kind.build(params, Dep, np.random.default_rng(s))
                for s in (1, 2, 1))
    assert not np.array_equal(a.bodies[0]["dev"], b.bodies[0]["dev"])
    assert not np.array_equal(a.bodies[0]["value"], b.bodies[0]["value"])
    assert not np.array_equal(a.order, b.order)
    assert np.array_equal(a.bodies[0]["dev"], a2.bodies[0]["dev"])
    assert a.payloads[0] == a2.payloads[0]
    assert a.interval == b.interval and a.lines == b.lines
    rule = cell["config"]["rules"]["thresholds"][0]
    reference = cells.load_kind(cell["config"]).reference
    for t in (a, b):
        fired = sum(int(reference.fires_threshold(
            rule, x["etype"], x["value"]).sum()) for x in t.bodies)
        share = fired / (32 * 1024)
        assert 0.007 < share < 0.013, share     # 1% +- 5 sigma


def _copy_with(tmp_path, change) -> str:
    """A copy of the benchmark with ``change(bench, here)`` applied to
    its BENCHMARK.json (and files added under ``here``)."""
    repo = str(tmp_path)
    here = os.path.join(repo, "benchmarks")
    shutil.copytree(os.path.join(cells.REPO, "benchmarks"), here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    change(bench, here)
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return repo


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """configs/x.json + traffic/y.json + layer_metrics/z.py + one entry
    of workloads (and of configs and per_layer) run as a new cell, with
    no file that was there edited."""
    def change(bench, here):
        config = cells.load_json(
            os.path.join(here, "configs", "fleet-10k.json"))
        config["fleet"]["devices"] = 400
        with open(os.path.join(here, "configs", "x.json"), "w") as f:
            json.dump(config, f)
        mix = cells.load_json(
            os.path.join(here, "traffic", "wire-steady.json"))
        mix.update(rate_events_per_s=2000.0, senders=2)
        with open(os.path.join(here, "traffic", "y.json"), "w") as f:
            json.dump(mix, f)
        with open(os.path.join(here, "layer_metrics", "z.py"), "w") as f:
            f.write("def read(run):\n    return float(run.width)\n")
        bench["configs"].append({
            "name": "x", "source": "test", "reduced": [],
            "file": "benchmarks/configs/x.json", "why": "test"})
        bench["workloads"].append({
            "name": "x.y", "config": "x", "traffic": "y", "chips": 1,
            "why": "test"})
        bench["per_layer"].append({
            "name": "z", "unit": "rows", "better": "higher",
            "source": "program_counter", "layer": "device",
            "moves": "events_per_s", "workloads": ["x.y"]})

    result, run = toy_run("x.y", trace=True,
                          repo=_copy_with(tmp_path, change))
    assert result["correct"] is True
    assert result["metrics"]["z"] == {"value": 256.0, "unit": "rows"}
    assert run.traffic["senders"] == 2


def _add_cell(config_of, traffic: str, chips: int = 1):
    """A change for ``_copy_with``: the cell ``x.<traffic>`` on a new
    configuration ``x`` = ``config_of(fleet-1m's file)``."""
    def change(bench, here):
        config = cells.load_json(
            os.path.join(here, "configs", "fleet-1m.json"))
        config_of(config)
        with open(os.path.join(here, "configs", "x.json"), "w") as f:
            json.dump(config, f)
        bench["configs"].append({
            "name": "x", "source": "test", "reduced": [],
            "file": "benchmarks/configs/x.json", "why": "test"})
        bench["workloads"].append({
            "name": f"x.{traffic}", "config": "x", "traffic": traffic,
            "chips": chips, "why": "test"})
    return change


def test_a_mesh_configuration_runs_on_four_virtual_devices(tmp_path):
    """The 4-chip cell is specified in PERF.md and lands with the PR
    that runs it on the chip; what it needs of the harness (a quarter of
    the fleet a shard, batches in shard-block order, the sharded-state
    check) is rehearsed here on a configuration with ``n_shards`` 4."""
    def mesh(config):
        config["config"]["pipeline"]["n_shards"] = 4
        config["programs"] = {
            "local_step|packed_pipeline_step|mapped": 1,
            "chain": "ring_depth"}

    result, run = toy_run("x.columns-saturate", repo=_copy_with(
        tmp_path, _add_cell(mesh, "columns-saturate", chips=4)))
    assert result["correct"] is True and result["failed"] == 0
    assert run.n_shards == 4 and result["device"]["count"] == 4
    assert set(result["metrics"]) == {"events_per_s", "setup_s"}


@pytest.mark.parametrize("traffic", ["wire-steady", "columns-saturate"])
def test_a_configuration_with_more_rules_is_a_file(tmp_path, traffic):
    """Three threshold rules and two zones that overlap: the deployment
    creates each, the reference counts a row once per family however
    many fire, and the roofline reads the counts from the
    configuration."""
    from benchmarks import roofline

    def more_rules(config):
        config["rules"] = {
            "thresholds": [{"op": "GT", "threshold": 90.0},
                           {"op": "LTE", "threshold": 20.0},
                           {"op": "GTE", "threshold": 95.0}],
            "zones": [{"lat": [-20.0, 20.0], "lon": [-20.0, 20.0]},
                      {"lat": [0.0, 40.0], "lon": [0.0, 40.0]}]}

    result, run = toy_run(f"x.{traffic}", repo=_copy_with(
        tmp_path, _add_cell(more_rules, traffic)))
    assert result["correct"] is True and result["failed"] == 0
    assert run.marks1["_dispatcher"]["threshold_alerts"] > 0
    assert roofline.rule_shape(run.config["rules"]) == {
        "rules": 3, "zones": 2, "vertices": 4}

"""What PR 27 added for the four-chip cell: the two readers of the
mesh's timer and counters, each against a hand-made run record (the
arithmetic; None where there is nothing to read: one chip, a program
without them, a window without an observation), and the cell's files,
chips and metric lists found by name."""

import os

import pytest

from benchmarks import cells
from benchmarks.harness import Run

CELL = "fleet-4m-mesh4.columns-saturate"
PLACE = "pipeline.stage_place_s"
ROWS = "ingest.shard_rows_emitted."
MARKS0 = {PLACE: (0.5, 100), **{f"{ROWS}{s}": 1000 for s in range(4)}}
MARKS1 = {PLACE: (0.9, 300), f"{ROWS}0": 5000, f"{ROWS}1": 4200,
          f"{ROWS}2": 5000, f"{ROWS}3": 4600}


def read(metric, marks0=MARKS0, marks1=MARKS1, n_shards=4):
    return cells.reader("layer_metrics", metric)(
        Run(marks0=marks0, marks1=marks1, n_shards=n_shards))


def test_place_is_the_timers_mean_over_the_window_in_ms():
    assert read("place_ms_per_plan") == pytest.approx(0.4 / 200 * 1e3)


def test_balance_is_the_emptiest_shard_over_the_fullest_in_percent():
    assert read("shard_balance_share") == pytest.approx(100 * 3200 / 4000)


@pytest.mark.parametrize("metric", ["place_ms_per_plan",
                                    "shard_balance_share"])
def test_none_when_nothing_was_observed_in_the_window(metric):
    assert read(metric, MARKS1, MARKS1) is None


@pytest.mark.parametrize("metric", ["place_ms_per_plan",
                                    "shard_balance_share"])
@pytest.mark.parametrize("n_shards", [1, 4])
def test_none_on_a_program_without_the_timer_or_the_counters(metric,
                                                             n_shards):
    """One chip registers neither; the parent commit has neither: the
    line leaves the metric out, it does not read 0 and does not raise."""
    assert read(metric, {}, {}, n_shards) is None


def test_the_cell_resolves_to_its_files_its_chips_and_its_metrics():
    cell = cells.resolve_cell(CELL)
    assert cell["chips"] == 4
    pipeline = cell["config"]["config"]["pipeline"]
    assert (pipeline["n_shards"], pipeline["registry_capacity"],
            pipeline["width"], pipeline["mtype_slots"]) \
        == (4, 1 << 22, 65536, 8)
    assert cell["config"]["fleet"]["devices"] == 131072
    assert cell["config"]["reduced"] == ["fleet.devices", "chips"]
    assert cell["traffic"]["kind"] == "columns-closed-loop"
    # the mix's own depth (PR 30's sweep), the one-chip columns cell's too
    assert cell["traffic"]["clients"] == 24 == cells.resolve_cell(
        "fleet-1m.columns-saturate")["traffic"]["clients"]
    assert "rate_events_per_s" not in cell["traffic"]   # no cells/ file
    assert [e["name"] for e, _ in cell["end_to_end"]] \
        == ["events_per_s", "setup_s"]
    layer = [e["name"] for e, _ in cell["per_layer"]]
    columns = [e["name"] for e, _ in
               cells.resolve_cell("fleet-1m.columns-saturate")["per_layer"]]
    # everything fleet-1m's columns cell reports, and the mesh's two
    assert layer == columns + ["place_ms_per_plan", "shard_balance_share"]
    assert {e["moves"] for e, _ in cell["per_layer"]} == {"events_per_s"}


def test_the_mesh_configuration_is_fleet_1m_but_for_the_mesh():
    here = cells.load_benchmark()
    files = {c["name"]: cells.load_json(os.path.join(cells.REPO, c["file"]))
             for c in here["configs"]}
    mesh, base = files["fleet-4m-mesh4"], files["fleet-1m"]
    for key in ("journal", "overload", "checkpoint"):
        assert mesh["config"][key] == base["config"][key]
    for key in ("rules", "guarantees", "calibration", "measurement",
                "fleet", "sample_devices"):
        assert mesh[key] == base[key], key
    changed = {k for k in base["config"]["pipeline"]
               if mesh["config"]["pipeline"][k]
               != base["config"]["pipeline"][k]}
    assert changed == {"n_shards", "registry_capacity"}


def test_every_metric_that_moves_events_per_s_lists_its_cells():
    bench = cells.load_benchmark()
    for m in bench["per_layer"]:
        assert "workloads" in m, m["name"]
        if m["moves"] == "events_per_s":
            assert CELL in m["workloads"], m["name"]

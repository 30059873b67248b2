"""A configuration of a second kind comes as files and entries.

``data/two-tenants/`` holds a configuration, its kind, the kind's plain
reference, a traffic mix and its traffic kind: two tenants created
through ``inst.tenants``, each with its own devices and a threshold
rule scoped to it, sends that carry the claimed tenant's id (one in
twenty the wrong one), and a cohort of devices a tenant whose only
event is nine hours old under a presence sweep every quarter second.
Laid over a copy of the benchmark with one entry each of ``configs``
and ``workloads``, and no file that was there edited, the cell runs
through ``harness.run_cell`` at toy size on the CPU and reads
``correct: true``; through ``control.run_control`` with each of the
kind's two faults underneath it reads ``correct: false`` at the
comparison the kind names.  What the rehearsal stands in for is
``tenants-8-presence`` (PERF.md section 7).
"""

import json
import os
import shutil
import time

import pytest

from benchmarks import cells, control, harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "two-tenants")
CELL = "two-tenants-presence.tenant-columns"
FAULTS = {"one-tenants-rows-as-the-others": "unregistered",
          "another-missing-after": "stored STATE_CHANGE events"}


@pytest.fixture(scope="module")
def repo(tmp_path_factory) -> str:
    """A copy of the benchmark with the second kind's files added."""
    repo = str(tmp_path_factory.mktemp("second-kind"))
    here = os.path.join(repo, "benchmarks")
    shutil.copytree(os.path.join(cells.REPO, "benchmarks"), here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for folder, _, files in os.walk(DATA):
        for name in files:
            to = os.path.join(here, os.path.relpath(folder, DATA), name)
            assert not os.path.exists(to), f"{to} was there: an edit"
            os.makedirs(os.path.dirname(to), exist_ok=True)
            shutil.copy(os.path.join(folder, name), to)
    bench = cells.load_benchmark()
    bench["configs"].append({
        "name": "two-tenants-presence", "source": "benchmarks/tests",
        "file": "benchmarks/configs/two-tenants-presence.json",
        "reduced": [], "why": "a second kind, rehearsed"})
    bench["workloads"].append({
        "name": CELL, "config": "two-tenants-presence",
        "traffic": "tenant-columns", "chips": 1, "why": "a second kind"})
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return repo


@pytest.fixture(scope="module")
def sound(repo):
    seen = {}
    result = harness.run_cell(
        cells.resolve_cell(CELL, repo), 3, 2.0, False, time.perf_counter(),
        require_tpu=False, on_run=lambda run: seen.update(run=run))
    return result, seen["run"]


def test_the_cell_of_the_second_kind_reads_correct(sound):
    result, run = sound
    assert result["correct"] is True, control.failed_comparisons(result)
    for what, (got, limit) in result["compared"].items():
        assert got == limit, what
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"events_per_s", "setup_s"}


def test_what_the_one_kind_could_not_judge_was_there_to_judge(sound):
    """Rows refused for their tenant, a report a silent device, alerts
    by tenant at different thresholds: none of them nought."""
    compared = sound[0]["compared"]
    refused = compared["unregistered (rows claiming a tenant that does not "
                       "own the device)"][1]
    assert refused > 0 and refused % 256 == 0
    assert compared["rows dead-lettered as unregistered"][1] == refused
    assert compared["stored STATE_CHANGE events = devices gone silent"] \
        == [32, 32]
    assert compared["silent devices reported once each"] == [32, 32]
    acme, globex = (compared[f"stored ALERT events of tenant {t}"][1]
                    for t in ("acme", "globex"))
    events = [compared[f"stored MEASUREMENT events of tenant {t}"][1]
              for t in ("acme", "globex")]
    # thresholds 90 and 60 over values uniform in [0, 100)
    assert 0.05 < acme / events[0] < 0.15
    assert 0.32 < globex / events[1] < 0.48


def test_the_second_cohort_is_sent_once_the_window_is_open(sound):
    """The first tenant's silent devices are reported in the priming
    pass (what a report compiles is compiled there); the second's get
    their one event as a measured send, so their report falls after
    the window opened, and compiled nothing (the harness's own
    comparison, held above)."""
    sends = sound[1].sends
    assert (sends.n[sends.measured] == 16).sum() == 1
    assert (sends.n[~sends.measured & (sends.n > 0)] == 16).sum() == 1


def test_the_harness_own_comparisons_are_made_for_the_second_kind(sound):
    """No kind switches off what holds whatever the deployment."""
    names = list(sound[0]["compared"])
    always = ["breaker at 'chained' with zero trips", "quarantined devices",
              "egress failures", "host_copy_errors",
              "native.build_fallbacks",
              "dead letters = sends shed by admission",
              "programs compiled inside the window"]
    assert [n for n in names if n in always] == always


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_control_of_the_second_kind_reads_not_correct(repo, fault):
    cell = cells.resolve_cell(CELL, repo)
    assert sorted(cells.load_kind(cell["config"]).FAULTS) == sorted(FAULTS)
    doc = control.run_control(cell, fault, 3, 2.0, time.perf_counter(),
                              require_tpu=False)
    assert doc["correct"] is False
    assert doc["must_fail"] == FAULTS[fault]
    assert doc["seen"], doc["failed_comparisons"]

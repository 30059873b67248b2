"""The readers of the program's stage spans (PR 24), each against a
hand-made run record: the arithmetic, None where there is nothing to
read (a program without the timer, a window without an observation),
and the ``wire_`` names resolving to the same reader."""

import pytest

from benchmarks import cells
from benchmarks.harness import Run

EGRESS = "pipeline.stage_egress_s"
WAIT = "pipeline.device_wait_s"
INFLIGHT = "pipeline.stage_inflight_wait_s"
BLOCKED = "pipeline.stage_dispatch_wait_s"
JOURNAL = "ingest.journal_append_s"
SAVE = "checkpoint.save_s"
STATE = "checkpoint.phase_state_s"

# (total seconds, observations) at the window's start and end
MARKS0 = {EGRESS: (1.0, 10), WAIT: (0.5, 9), INFLIGHT: (2.0, 10),
          BLOCKED: (0.25, 12), JOURNAL: (0.010, 5), SAVE: (0.0, 0),
          STATE: (0.0, 0)}
MARKS1 = {EGRESS: (3.8, 30), WAIT: (2.9, 27), INFLIGHT: (6.0, 30),
          BLOCKED: (4.25, 40), JOURNAL: (0.060, 30), SAVE: (4.2, 1),
          STATE: (3.1, 1)}

# metric -> what the marks above give it, in ms
EXPECTED = {
    "device_wait_ms_per_plan": (2.9 - 0.5) / 20 * 1e3,
    "egress_host_ms_per_plan": ((3.8 - 1.0) - (2.9 - 0.5)) / 20 * 1e3,
    "inflight_wait_ms_per_plan": (6.0 - 2.0) / 20 * 1e3,
    "dispatch_blocked_ms": (4.25 - 0.25) * 1e3,
    "journal_ms_per_payload": (0.060 - 0.010) / 25 * 1e3,
    "checkpoint_save_ms": 4200.0,
    "checkpoint_state_ms": 3100.0,
}
ALIASES = {
    "wire_device_wait_ms_per_plan": "device_wait_ms_per_plan",
    "wire_egress_host_ms_per_plan": "egress_host_ms_per_plan",
    "wire_inflight_wait_ms_per_plan": "inflight_wait_ms_per_plan",
}
# the timer each metric cannot do without
NEEDS = {
    "device_wait_ms_per_plan": WAIT, "egress_host_ms_per_plan": WAIT,
    "inflight_wait_ms_per_plan": INFLIGHT, "dispatch_blocked_ms": BLOCKED,
    "journal_ms_per_payload": JOURNAL, "checkpoint_save_ms": SAVE,
    "checkpoint_state_ms": STATE,
}


def read(metric, marks0=MARKS0, marks1=MARKS1):
    return cells.reader("layer_metrics", metric)(
        Run(marks0=marks0, marks1=marks1))


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_arithmetic(metric):
    assert read(metric) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("alias", sorted(ALIASES))
def test_wire_names_resolve_to_the_same_reading(alias):
    assert read(alias) == pytest.approx(EXPECTED[ALIASES[alias]])


def test_device_wait_and_egress_host_make_up_the_outside_view():
    assert (read("device_wait_ms_per_plan")
            + read("egress_host_ms_per_plan")
            == pytest.approx(read("egress_wait_ms_per_plan")))


@pytest.mark.parametrize("metric", sorted(NEEDS))
def test_none_on_a_program_without_the_timer(metric):
    """The parent commit has none of these timers: its line leaves the
    metric out, it does not read 0."""
    gone = NEEDS[metric]
    marks0 = {k: v for k, v in MARKS0.items() if k != gone}
    marks1 = {k: v for k, v in MARKS1.items() if k != gone}
    assert read(metric, marks0, marks1) is None


@pytest.mark.parametrize("metric", sorted(set(NEEDS) - {"dispatch_blocked_ms"}))
def test_none_when_nothing_was_observed_in_the_window(metric):
    """No plan egressed, no payload journaled, no checkpoint ended."""
    assert read(metric, MARKS1, MARKS1) is None


def test_dispatch_blocked_is_a_total_and_reads_zero_when_never_blocked():
    assert read("dispatch_blocked_ms", MARKS1, MARKS1) == 0.0


def test_every_new_entry_has_its_reader_and_its_cells():
    bench = cells.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    wire = ["fleet-1m.wire-steady", "fleet-10k.wire-steady"]
    for name in list(EXPECTED) + list(ALIASES):
        entry = entries[name]
        assert entry["unit"] == "ms" and entry["better"] == "lower"
        assert entry["source"] == "program_span"
        if name.startswith("wire_") or name == "journal_ms_per_payload":
            assert entry["workloads"] == wire
            assert entry["moves"] == "latency_p50_ms"
        else:
            assert entry["moves"] == "events_per_s"
    assert entries["dispatch_blocked_ms"]["workloads"] == [
        w["name"] for w in bench["workloads"]]

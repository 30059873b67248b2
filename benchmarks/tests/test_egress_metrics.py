"""The readers of the process layer, of the intake lock's waiters and
holder, and of the egress worker's legs, each against a hand-made run
record: the arithmetic, None where the program keeps no such timer
(the parent commit), what a window with nothing observed reads, and
the unattributed remainder as the stage less its named children."""

import pytest

from benchmarks import cells
from benchmarks.harness import Run

EGRESS = "pipeline.stage_egress_s"
WAIT = "pipeline.device_wait_s"
METER = "pipeline.stage_meter_s"
PERSIST = "pipeline.egress_persist_s"
SEAL = "store.inline_seal_s"
OUTBOUND = "pipeline.egress_outbound_s"
REINJECT = "pipeline.egress_reinject_s"
REINJECT_WAIT = "pipeline.lock_wait_reinject_s"
WIRE_WAIT = "pipeline.lock_wait_wire_s"
GATE = "pipeline.commit_gate_s"
STALL = "runtime.stall_s"
STALL_CPU = "runtime.stall_cpu_s"
GC = "runtime.gc_full_s"

# (total seconds, observations) at the window's start and end: 20
# plans egressed in the window
MARKS0 = {EGRESS: (1.0, 10), WAIT: (0.1, 10), METER: (0.01, 10),
          PERSIST: (0.5, 10), SEAL: (0.2, 2), OUTBOUND: (0.05, 10),
          REINJECT: (0.2, 5), REINJECT_WAIT: (0.02, 5),
          WIRE_WAIT: (0.010, 100), GATE: (0.040, 8),
          STALL: (0.3, 1), STALL_CPU: (0.1, 1), GC: (0.05, 3)}
MARKS1 = {EGRESS: (2.4, 30), WAIT: (0.14, 30), METER: (0.03, 30),
          PERSIST: (1.3, 30), SEAL: (0.6, 6), OUTBOUND: (0.09, 30),
          REINJECT: (0.52, 13), REINJECT_WAIT: (0.10, 13),
          WIRE_WAIT: (0.130, 140), GATE: (0.100, 20),
          STALL: (1.8, 3), STALL_CPU: (0.4, 3), GC: (0.11, 5)}

# metric -> what the marks above give it
EXPECTED = {
    "stall_ms_in_window": 1.5 * 1e3,
    "stall_cpu_cores": 0.3 / 1.5,
    "gc_full_ms_in_window": 0.06 * 1e3,
    "wire_commit_gate_ms_per_commit": 0.06 / 12 * 1e3,
    "wire_intake_lock_wait_ms_per_payload": 0.12 / 40 * 1e3,
    "egress_persist_ms_per_plan": 0.8 / 20 * 1e3,
    "egress_inline_seal_ms_per_plan": 0.4 / 20 * 1e3,
    "egress_outbound_ms_per_plan": 0.04 / 20 * 1e3,
    "egress_reinject_ms_per_plan": 0.32 / 20 * 1e3,
    "egress_reinject_lock_wait_ms_per_plan": 0.08 / 20 * 1e3,
    "egress_unattributed_ms_per_plan":
        (1.4 - 0.04 - 0.8 - 0.04 - 0.32 - 0.02) / 20 * 1e3,
}
# the timer each metric cannot do without
NEEDS = {
    "stall_ms_in_window": STALL,
    "stall_cpu_cores": STALL_CPU,
    "gc_full_ms_in_window": GC,
    "wire_commit_gate_ms_per_commit": GATE,
    "wire_intake_lock_wait_ms_per_payload": WIRE_WAIT,
    "egress_persist_ms_per_plan": PERSIST,
    "egress_inline_seal_ms_per_plan": SEAL,
    "egress_outbound_ms_per_plan": OUTBOUND,
    "egress_reinject_ms_per_plan": REINJECT,
    "egress_reinject_lock_wait_ms_per_plan": REINJECT_WAIT,
    "egress_unattributed_ms_per_plan": PERSIST,
}
# what a window in which the timers exist and gained nothing reads
IDLE = {
    "stall_ms_in_window": 0.0,
    "stall_cpu_cores": 0.0,
    "gc_full_ms_in_window": 0.0,
}
ALL = ["fleet-1m.wire-steady", "fleet-1m.columns-saturate",
       "fleet-10k.wire-steady", "fleet-4m-mesh4.columns-saturate",
       "tenants-8-presence.wire-tenants"]
COLUMNS = ["fleet-1m.columns-saturate", "fleet-4m-mesh4.columns-saturate"]
WIRE = ["fleet-1m.wire-steady", "fleet-10k.wire-steady",
        "tenants-8-presence.wire-tenants"]
# metric -> (layer, moves, workloads)
ENTRIES = {
    "stall_ms_in_window": ("process", "events_per_s", ALL),
    "stall_cpu_cores": ("process", "events_per_s", ALL),
    "gc_full_ms_in_window": ("process", "events_per_s", ALL),
    "wire_commit_gate_ms_per_commit":
        ("durability and background work", "latency_p50_ms", WIRE),
    "wire_intake_lock_wait_ms_per_payload":
        ("dispatcher intake", "latency_p50_ms", WIRE),
}


def read(metric, marks0=MARKS0, marks1=MARKS1):
    return cells.reader("layer_metrics", metric)(
        Run(marks0=marks0, marks1=marks1))


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_arithmetic(metric):
    assert read(metric) == pytest.approx(EXPECTED[metric])


def test_unattributed_is_the_stage_less_its_named_children():
    host = read("egress_host_ms_per_plan")
    legs = sum(read(m) for m in ("egress_persist_ms_per_plan",
                                 "egress_outbound_ms_per_plan",
                                 "egress_reinject_ms_per_plan"))
    # the fold runs once a plan here, so its mean is per plan too
    meter = read("tenant_meter_ms_per_plan")
    assert read("egress_unattributed_ms_per_plan") == pytest.approx(
        host - legs - meter)


def test_the_children_read_inside_their_parents():
    assert (read("egress_inline_seal_ms_per_plan")
            <= read("egress_persist_ms_per_plan"))
    assert (read("egress_reinject_lock_wait_ms_per_plan")
            <= read("egress_reinject_ms_per_plan"))


@pytest.mark.parametrize("metric", sorted(NEEDS))
def test_none_on_a_program_without_the_timer(metric):
    """The parent commit has none of these timers: its line leaves the
    metric out, it does not read 0."""
    gone = NEEDS[metric]
    marks0 = {k: v for k, v in MARKS0.items() if k != gone}
    marks1 = {k: v for k, v in MARKS1.items() if k != gone}
    assert read(metric, marks0, marks1) is None


@pytest.mark.parametrize("metric", sorted(NEEDS))
def test_a_window_that_observed_nothing(metric):
    """No stall, no collection, no plan egressed, no payload taken, no
    commit made: the process metrics read 0, the means nothing."""
    assert read(metric, MARKS1, MARKS1) == IDLE.get(metric)


def test_a_leg_that_never_ran_reads_zero_not_nothing():
    """The valve shut all window: a reading of 0 ms a plan."""
    marks1 = dict(MARKS1, **{SEAL: MARKS0[SEAL]})
    assert read("egress_inline_seal_ms_per_plan", MARKS0, marks1) == 0.0


def test_stall_cores_can_pass_one():
    """Two threads computing through a stall: not a share."""
    marks1 = dict(MARKS1, **{STALL_CPU: (0.1 + 3.0, 3)})
    assert read("stall_cpu_cores", MARKS0, marks1) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_every_new_entry_has_its_reader_and_its_cells(metric):
    entry = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}[metric]
    layer, moves, workloads = ENTRIES.get(
        metric, ("D2H and egress", "events_per_s", COLUMNS))
    assert entry["source"] == "program_span"
    assert (entry["layer"], entry["moves"], entry["workloads"]) == (
        layer, moves, workloads)
    assert (entry["unit"], entry["better"]) == (
        "cores" if metric == "stall_cpu_cores" else "ms", "lower")

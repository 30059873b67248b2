"""The process layer (tier-1, CPU): the stall witness on the dispatcher
loop's waits, full collections as spans, and the marker primitive.

``StallWitness`` is driven with fake clocks, so a stall costs no sleep:
a wait :data:`STALL_S` or more past its timeout marks ``runtime.stall_s``,
observes the CPU across it into ``runtime.stall_cpu_s`` and hands one
record to its report (the dispatcher's ``stall`` flight record, dumped
from :data:`DUMP_STALL_S` on); a shorter one leaves nothing.
"""

import gc
import glob
import json
import threading
import time
import types

import jax
import pytest

from sitewhere_tpu.runtime.flightrec import FlightRecorder, parse_snapshot
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.process import (
    DUMP_STALL_S,
    RUSAGE_FIELDS,
    STALL_S,
    FullCollections,
    StallWitness,
)

from time_limit import wait_until

TIMEOUT = 0.004


class _Clocks:
    """A wall clock, a CPU clock and kernel counters that an
    ``_Event.wait`` moves on by what the test says the wait took."""

    def __init__(self):
        self.wall = 100.0
        self.cpu = 5.0
        self.rusage = tuple(10 * k for k in range(len(RUSAGE_FIELDS)))
        self.reads = 0   # of the CPU clock

    def witness(self, metrics, **kw):
        return StallWitness(metrics, clock=lambda: self.wall,
                            cpu_clock=self.read_cpu,
                            rusage=self.read_rusage, **kw)

    def read_cpu(self):
        self.reads += 1
        return self.cpu

    def read_rusage(self):
        """The counters as ``getrusage`` names them."""
        return types.SimpleNamespace(**{
            attr: v for (_, attr), v in zip(RUSAGE_FIELDS, self.rusage)})


class _Event:
    """``threading.Event.wait`` that takes ``timeout + late`` seconds of
    the fake wall clock and ``cpu`` seconds of the fake CPU clock, and
    adds ``counts`` to the kernel counters."""

    def __init__(self, clocks, late, cpu=0.0, counts=None, gc_s=0.0,
                 collections=None):
        self.clocks, self.late, self.cpu = clocks, late, cpu
        self.counts = counts or (0,) * len(RUSAGE_FIELDS)
        self.gc_s, self.collections = gc_s, collections

    def wait(self, timeout):
        self.clocks.wall += timeout + self.late
        self.clocks.cpu += self.cpu
        self.clocks.rusage = tuple(
            a + b for a, b in zip(self.clocks.rusage, self.counts))
        if self.gc_s:   # as FullCollections' callback leaves one
            self.collections.total += self.gc_s
            self.collections.done.append(self.gc_s)
        return False


def test_a_wait_a_quarter_second_late_is_one_stall(tmp_path):
    reg = MetricsRegistry()
    clocks = _Clocks()
    rec = FlightRecorder(data_dir=str(tmp_path), metrics=reg)
    witness = clocks.witness(
        reg, report=lambda r: rec.anomaly("stall", json.dumps(r)),
        save_probe=lambda seconds: seconds > 0.2)
    counts = (2, 300, 1, 7, 16, 8)
    event = _Event(clocks, late=0.25, cpu=0.05, counts=counts, gc_s=0.03,
                   collections=witness.full_collections)
    assert witness.wait(event, TIMEOUT) is False
    stall, cpu = reg.timer("runtime.stall_s"), reg.timer("runtime.stall_cpu_s")
    assert stall.count == 1 and stall.total == pytest.approx(0.25)
    # the cores busy over the 254 ms the deltas span, for the 250 late
    assert cpu.count == 1
    assert cpu.total == pytest.approx(0.05 / 0.254 * 0.25)
    # the wake drained the collection into its timer
    assert reg.timer("runtime.gc_full_s").total == pytest.approx(0.03)
    (record,) = witness.recent
    assert record["at"] == pytest.approx(time.time(), abs=60)
    assert record["late_ms"] == pytest.approx(250.0)
    assert record["wait_ms"] == pytest.approx(254.0)
    assert record["span_ms"] == pytest.approx(254.0)
    assert record["cpu_cores"] == pytest.approx(0.05 / 0.254, abs=1e-4)
    assert record["gc_full_ms"] == pytest.approx(30.0)
    assert record["save_running"] is True
    assert {name: record[name] for name, _ in RUSAGE_FIELDS} == dict(
        zip([name for name, _ in RUSAGE_FIELDS], counts))
    # the flight recorder's stall anomaly carries the record
    (path,) = glob.glob(str(tmp_path / "flightrec" / "*-stall.jsonl"))
    with open(path, "rb") as f:
        header = parse_snapshot(f.read())["header"]
    assert header["reason"] == "stall"
    assert json.loads(header["detail"]) == record


@pytest.mark.parametrize("late", [0.0, 0.05, STALL_S - 1e-6])
def test_a_wait_less_late_than_the_threshold_leaves_nothing(late):
    reg = MetricsRegistry()
    clocks = _Clocks()
    reports = []
    witness = clocks.witness(reg, report=reports.append)
    assert witness.wait(_Event(clocks, late=late, cpu=0.01), TIMEOUT) is False
    assert reg.timer("runtime.stall_s").count == 0
    assert reg.timer("runtime.stall_cpu_s").count == 0
    assert not reports and not witness.recent


def test_the_costlier_counters_are_read_once_a_half_threshold():
    reg = MetricsRegistry()
    clocks = _Clocks()
    witness = clocks.witness(reg)
    on_time = _Event(clocks, late=0.0, cpu=0.001, counts=(0, 1, 1, 0, 0, 0))
    wakes = 100
    for _ in range(wakes):
        witness.wait(on_time, TIMEOUT)
    # one baseline per STALL_S / 2 of wall clock, not one a wake
    assert clocks.reads == pytest.approx(wakes * TIMEOUT / (STALL_S / 2),
                                         abs=1)
    # a stall's deltas span the wait and at most STALL_S / 2 before it
    witness.wait(_Event(clocks, late=0.3, cpu=0.0,
                        counts=(0, 5, 0, 0, 0, 0)), TIMEOUT)
    (record,) = witness.recent
    assert 304.0 <= record["span_ms"] < 304.0 + STALL_S / 2 * 1e3
    span_s = record["span_ms"] / 1e3
    before = round((span_s - 0.304) / TIMEOUT)   # on-time wakes inside
    assert record["minor_faults"] == 5 + before
    assert record["cpu_cores"] == pytest.approx(before * 0.001 / span_s,
                                                abs=1e-4)


def test_the_witness_returns_what_the_wait_returns():
    reg = MetricsRegistry()
    witness = StallWitness(reg)
    stop = threading.Event()
    assert witness.wait(stop, 0.001) is False
    stop.set()
    assert witness.wait(stop, 1.0) is True
    assert reg.timer("runtime.stall_s").count == 0


def test_a_mark_is_an_observation_and_a_profiler_event(tmp_path):
    timer = MetricsRegistry().timer("runtime.stall_s")
    jax.profiler.start_trace(str(tmp_path))
    try:
        timer.mark(0.3, late_ms=300.0)
    finally:
        jax.profiler.stop_trace()
    assert timer.count == 1 and timer.total == pytest.approx(0.3)
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    events = [dict(ev.stats)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name == "runtime.stall_s"]
    assert len(events) == 1 and events[0]["late_ms"] == 300.0


# ---------------------------------------------------------------------------
# full collections
# ---------------------------------------------------------------------------

@pytest.fixture
def no_automatic_gc():
    """Only the test's own collections run while it does."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def test_a_full_collection_is_one_observation(no_automatic_gc):
    reg = MetricsRegistry()
    full = FullCollections(reg)
    timer = reg.timer("runtime.gc_full_s")
    full.install()
    try:
        full.install()   # once only
        assert gc.callbacks.count(full) == 1
        gc.collect(2)
        # timed inside the collection, observed only by a drain
        assert timer.count == 0 and len(full.done) == 1
        full.drain()
        assert timer.count == 1 and timer.total > 0.0
        assert full.total == pytest.approx(timer.total)
        gc.collect(0)
        gc.collect(1)
        full.drain()
        assert timer.count == 1
    finally:
        full.remove()
    assert full not in gc.callbacks
    gc.collect(2)
    full.drain()
    assert timer.count == 1
    full.remove()   # twice is harmless


def test_a_full_collection_is_a_profiler_span(tmp_path, no_automatic_gc):
    full = FullCollections(MetricsRegistry())
    full.install()
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
        full.remove()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans = [ev.duration_ns
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "runtime.gc_full_s"]
    assert len(spans) == 1
    assert spans[0] / 1e9 == pytest.approx(full.total, rel=0.5)


def test_a_full_collection_under_the_timers_lock_does_not_wait_for_it(
        no_automatic_gc):
    """A collection can run wherever the interpreter allocates, also on
    a thread that holds ``runtime.gc_full_s``'s lock (a scrape reading
    its percentiles): the callback must not take that lock."""
    reg = MetricsRegistry()
    full = FullCollections(reg)
    timer = reg.timer("runtime.gc_full_s")
    full.install()
    done = threading.Event()

    def scrape_that_collects():
        with timer._lock:
            gc.collect(2)
        done.set()

    try:
        threading.Thread(target=scrape_that_collects, daemon=True).start()
        assert done.wait(10.0), "the collection waited for the lock"
    finally:
        full.remove()
    assert timer.count == 1 and timer.percentile(0.5) > 0.0


# ---------------------------------------------------------------------------
# the dispatcher's wiring
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def inst(tmp_path_factory):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    cfg = Config({
        "instance": {"id": "stall",
                     "data_dir": str(tmp_path_factory.mktemp("stall"))},
        "pipeline": {"width": 64, "registry_capacity": 256,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "checkpoint": {"interval_s": 3600.0},
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    try:
        yield inst
    finally:
        inst.stop()


def test_the_dispatcher_times_full_collections_while_it_runs(
        inst, no_automatic_gc):
    d = inst.dispatcher
    timer = inst.metrics.timer("runtime.gc_full_s")
    assert d.stall_witness.full_collections in gc.callbacks
    n = timer.count
    gc.collect(2)
    # the loop's next wake drains it into the timer
    assert wait_until(lambda: timer.count == n + 1)


def test_the_loop_waits_through_the_witness(inst):
    d = inst.dispatcher
    w = d.stall_witness
    assert w.save_probe == inst.checkpointer.saving_within
    assert w.report is not None
    waits = []
    real = w._clock

    def counted():
        waits.append(1)
        return real()

    w._clock = counted
    try:
        # the loop thread reads the wall clock twice a wake
        assert wait_until(lambda: len(waits) >= 6)
    finally:
        w._clock = real


def test_a_reported_stall_is_a_flight_record_and_no_dump(inst):
    record = {"late_ms": 150.0, "cpu_cores": 0.0}
    rec = inst.flightrec
    written = rec.stats()["snapshots_written"]
    inst.dispatcher._on_stall(record)
    assert any(r.get("kind") == "stall" and r["late_ms"] == 150.0
               for r in rec.recent(50))
    assert rec.stats()["snapshots_written"] == written


def test_a_reported_stall_lands_in_the_flight_recorder(inst):
    record = {"late_ms": DUMP_STALL_S * 1e3, "cpu_cores": 0.0}
    rec = inst.flightrec
    # a real stall of this busy host may have dumped moments ago: lift
    # the per-reason rate limit for the one dump under test
    interval, rec.min_snapshot_interval_s = rec.min_snapshot_interval_s, 0.0
    try:
        written = rec.stats()["snapshots_written"]
        inst.dispatcher._on_stall(record)
        # dumped off the calling thread: the count moves once the file
        # is whole
        assert wait_until(lambda: rec.stats()["snapshots_written"] > written)
    finally:
        rec.min_snapshot_interval_s = interval
    name = max(s["name"] for s in rec.snapshots()
               if s["name"].endswith("-stall.jsonl"))
    header = parse_snapshot(rec.read_snapshot(name))["header"]
    assert json.loads(header["detail"]) == record


def test_a_save_overlaps_a_wait_it_ran_in(inst):
    cp = inst.checkpointer
    was = cp.last_saved_at
    try:
        cp.last_saved_at = time.time() - 100.0
        assert not cp.saving_within(1.0)
        assert cp.saving_within(200.0)
        with cp._save_lock:
            assert cp.saving_within(1.0)
    finally:
        cp.last_saved_at = was


def test_full_collections_are_not_timed_after_stop(tmp_path, no_automatic_gc):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    cfg = Config({
        "instance": {"id": "stall-stop", "data_dir": str(tmp_path)},
        "pipeline": {"width": 64, "registry_capacity": 256,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "checkpoint": {"interval_s": 3600.0},
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    full = inst.dispatcher.stall_witness.full_collections
    try:
        assert full in gc.callbacks
    finally:
        inst.stop()
    assert full not in gc.callbacks
    timer = inst.metrics.timer("runtime.gc_full_s")
    n = timer.count
    gc.collect(2)
    assert timer.count == n

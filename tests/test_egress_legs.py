"""The egress worker leg by leg, and the intake lock's waiters and
holder (tier-1, CPU).

Each leg of ``_fan_out`` that a served cell runs — the store append,
the outbound submit, the re-injection of derived alerts — is a registry
timer (a profiler span of the same name, ``seq=plan.seq``) and keeps
the request tracer's span it always had; the valve's inline seal is a
child of the append; ``_take`` observes its wait for ``_lock`` into the timer
its caller names; the commit gate is timed once a commit.  The
long-lived threads carry an OS name the profiler shows.
"""

import json
import os
import sys
import threading
import time

import pytest

from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.process import name_os_thread
from sitewhere_tpu.schema import ComparisonOp

from test_segment_store import make_cols, make_store
from time_limit import wait_until

WIDTH = 64
LEGS = ("pipeline.egress_persist_s", "pipeline.egress_outbound_s",
        "pipeline.egress_reinject_s")


def _instance(tmp_path, **sections):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.outbound.connectors import CallbackConnector
    from sitewhere_tpu.runtime.config import Config

    cfg = Config(dict({
        "instance": {"id": "legs", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": WIDTH, "registry_capacity": 256,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1,
                     # the chip's wiring: egress on its own worker
                     "egress_offload": True},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "checkpoint": {"interval_s": 3600.0},
    }, **sections), apply_env=False)
    inst = Instance(cfg)
    inst.outbound.add_connector(CallbackConnector(
        "legs-client", lambda cols, mask: None))
    inst.start()
    dm = inst.device_management
    dm.create_device_type(token="sensor", name="S")
    dm.create_device(token="d-0", device_type="sensor")
    dm.create_device_assignment(device="d-0")
    inst.rules.create_rule(mtype="temp", op=ComparisonOp.GT,
                           threshold=90.0, alert_type="overheat")
    return inst


def _payload(n, hot=0, ts=1_753_800_000):
    """``n`` measurements of ``d-0``, the first ``hot`` of them over the
    rule's threshold."""
    return "\n".join(json.dumps({
        "deviceToken": "d-0", "type": "Measurement",
        "request": {"name": "temp", "value": 95.0 if k < hot else 20.0,
                    "eventDate": ts + k}})
        for k in range(n)).encode()


def _counts(reg):
    return {name: reg.timer(name).count
            for name in LEGS + ("pipeline.stage_egress_s",
                                "pipeline.lock_wait_reinject_s")}


@pytest.fixture(scope="module")
def fired(tmp_path_factory):
    """An instance after one payload whose plan stores, submits and
    fires one alert, and the alert's own plan; its leg counts before
    and after."""
    inst = _instance(tmp_path_factory.mktemp("fired"))
    try:
        d, reg = inst.dispatcher, inst.metrics
        before = _counts(reg)
        d.ingest_wire_lines(_payload(8, hot=1))
        d.flush()
        d.flush()   # the second flush carries the derived alert
        assert d.metrics_snapshot()["derived_alerts"] == 1
        yield inst, before, _counts(reg)
    finally:
        inst.stop()


def test_a_plan_that_stores_submits_and_fires_observes_each_leg_once(fired):
    _, before, after = fired
    grew = {name: after[name] - before[name] for name in after}
    # the payload's plan and its alert's plan both store and submit;
    # only the payload's fired a rule
    assert grew["pipeline.stage_egress_s"] == 2
    assert grew["pipeline.egress_persist_s"] == 2
    assert grew["pipeline.egress_outbound_s"] == 2
    assert grew["pipeline.egress_reinject_s"] == 1
    assert grew["pipeline.lock_wait_reinject_s"] == 1


def test_the_legs_are_children_of_the_egress_stage(fired):
    inst, _, _ = fired
    reg = inst.metrics
    egress = reg.timer("pipeline.stage_egress_s").total
    children = sum(reg.timer(name).total for name in LEGS
                   + ("pipeline.device_wait_s", "pipeline.stage_meter_s"))
    assert 0.0 < children <= egress
    assert (reg.timer("pipeline.lock_wait_reinject_s").total
            <= reg.timer("pipeline.egress_reinject_s").total)


def _host_events(trace_dir):
    """(host line, event name, start ns, end ns, stats) of a capture."""
    import glob

    import jax

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    return [(line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.mark.skipif(not sys.platform.startswith("linux"),
                    reason="thread names are set on Linux only")
def test_the_legs_are_profiler_spans_on_the_named_egress_thread(tmp_path):
    import jax

    inst = _instance(tmp_path / "inst")
    try:
        d = inst.dispatcher
        d.ingest_wire_lines(_payload(8, hot=1))
        d.flush()
        d.flush()
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            d.ingest_wire_lines(_payload(8, hot=1, ts=1_753_800_100))
            d.flush()
            d.flush()
        finally:
            jax.profiler.stop_trace()
    finally:
        inst.stop()
    events = _host_events(tmp_path / "trace")
    for name in LEGS + ("pipeline.lock_wait_reinject_s",):
        mine = [e for e in events if e[1] == name]
        assert mine and {e[0] for e in mine} == {"sw-egress"}, name
        assert all("seq" in e[4] or name.startswith("pipeline.lock")
                   for e in mine)
    # the reinject leg's lock wait lies inside the leg, on its thread
    (leg,) = [e for e in events if e[1] == "pipeline.egress_reinject_s"]
    (wait,) = [e for e in events if e[1] == "pipeline.lock_wait_reinject_s"]
    assert leg[2] <= wait[2] <= wait[3] <= leg[3]


def test_a_forced_valve_observes_the_inline_seal(tmp_path):
    reg = MetricsRegistry()
    store = make_store(tmp_path, flush_rows=64, n_shards=1, workers=1,
                       metrics=reg)
    seal = reg.timer("store.inline_seal_s")
    # the sealer never started: each full buffer queues a job, and past
    # 4 + workers jobs the writer seals one on its own thread
    bound = 4 + store.sealer.n_workers
    for k in range(bound):
        store.append_columns(make_cols(64, ts0=1_753_900_000 + 64 * k))
    assert store.sealer.queue_depth() == bound and seal.count == 0
    store.append_columns(make_cols(64, ts0=1_753_900_000 + 64 * bound))
    assert seal.count == 1 and seal.total > 0.0
    assert store.sealer.sealed_segments == 1
    store.flush(sync=True)


def _waiting_at_the_lock(ident):
    """Is thread ``ident`` inside ``_take`` at its ``with self._lock``,
    i.e. blocked on the acquire (a C call leaves no frame of its own)?"""
    frame = sys._current_frames().get(ident)
    if frame is None or frame.f_code.co_name != "_take":
        return False
    return "with self._lock" in _source_line(frame)


def _source_line(frame):
    import linecache

    return linecache.getline(frame.f_code.co_filename, frame.f_lineno)


def test_a_wire_payload_behind_a_held_lock_observes_its_wait(tmp_path):
    inst = _instance(tmp_path)
    try:
        d, reg = inst.dispatcher, inst.metrics
        wire = reg.timer("pipeline.lock_wait_wire_s")
        reinject = reg.timer("pipeline.lock_wait_reinject_s")
        d.ingest_wire_lines(_payload(4))   # compiles, registers the shape
        d.flush()
        n0, r0 = wire.count, reinject.count
        sender = threading.Thread(
            target=d.ingest_wire_lines,
            args=(_payload(4, ts=1_753_800_100),), daemon=True)
        with d._lock:
            sender.start()
            assert wait_until(lambda: _waiting_at_the_lock(sender.ident))
            time.sleep(0.05)
        sender.join(timeout=30)
        assert not sender.is_alive()
        d.flush()
        assert wire.count == n0 + 1
        assert wire.percentile(1.0) >= 0.05
        assert reinject.count == r0   # nothing fired, nothing re-injected
    finally:
        inst.stop()


def test_an_arrays_intake_observes_no_wire_lock_wait(tmp_path):
    import numpy as np

    inst = _instance(tmp_path)
    try:
        d, reg = inst.dispatcher, inst.metrics
        wire = reg.timer("pipeline.lock_wait_wire_s")
        batch = reg.timer("pipeline.stage_batch_s")
        n0, b0 = wire.count, batch.count
        d.ingest_arrays(device_id=np.zeros(WIDTH, np.int32))
        d.flush()
        assert batch.count > b0   # the intake took the lock and emitted
        assert wire.count == n0
        d.ingest_wire_lines(_payload(4))
        d.flush()
        assert wire.count == n0 + 1
    finally:
        inst.stop()


def test_the_commit_gate_is_observed_once_a_commit(tmp_path):
    inst = _instance(tmp_path)
    try:
        d, reg = inst.dispatcher, inst.metrics
        gate = reg.timer("pipeline.commit_gate_s")
        reader = d.journal_reader
        commits = []
        commit = reader.commit

        def counted(upto):
            commits.append(upto)
            return commit(upto)

        reader.commit = counted
        d._maybe_commit_offset()   # nothing egressed: returns early
        assert gate.count == 0
        for i in range(3):
            d.ingest_wire_lines(_payload(4, ts=1_753_800_000 + 10 * i))
            d.flush()
        assert wait_until(lambda: reader.committed
                          == d._max_egressed_ref + 1)
        assert commits and gate.count == len(commits)
        d._maybe_commit_offset()   # nothing new past the offset
        assert gate.count == len(commits)
    finally:
        inst.stop()


def test_a_fetch_over_the_soft_budget_keeps_the_plans_flight_record(
        tmp_path):
    inst = _instance(tmp_path)
    try:
        d, rec = inst.dispatcher, inst.flightrec
        d.ingest_wire_lines(_payload(4))   # compiles, registers the shape
        d.flush()
        assert not [r for r in rec.recent(100)
                    if r.get("kind") == "slow-fetch"]
        soft, d.watchdog.soft_s = d.watchdog.soft_s, 0.0
        try:
            d.ingest_wire_lines(_payload(4, ts=1_753_800_100))
            d.flush()
        finally:
            d.watchdog.soft_s = soft
        slow = [r for r in rec.recent(100) if r.get("kind") == "slow-fetch"]
        assert slow and all(isinstance(r["seq"], int) and r["fetch_ms"] > 0
                            for r in slow)
        # the ring is dumped off the egress thread
        assert wait_until(lambda: any(
            s["name"].endswith("-device-slow-fetch.jsonl")
            for s in rec.snapshots()))
    finally:
        inst.stop()


def test_a_sampled_trace_keeps_the_egress_span_names(tmp_path):
    inst = _instance(tmp_path, tracing={"sample_rate": 1.0})
    try:
        d = inst.dispatcher
        d.ingest_wire_lines(_payload(8, hot=1))
        d.flush()
        d.flush()
        names = {s["name"] for s in inst.tracer.recent(limit=1000)}
        assert {"egress.persist", "egress.outbound",
                "egress.derived-alerts"} <= names
    finally:
        inst.stop()


# ---------------------------------------------------------------------------
# the threads' OS names
# ---------------------------------------------------------------------------

def _comm(native_id):
    with open(f"/proc/self/task/{native_id}/comm") as f:
        return f.read().strip()


linux = pytest.mark.skipif(not sys.platform.startswith("linux"),
                           reason="thread names are set on Linux only")


@linux
def test_a_thread_names_itself_cut_to_fifteen_bytes():
    seen = {}

    def body():
        name_os_thread("sw-a-name-longer-than-fifteen")
        seen["comm"] = _comm(threading.get_native_id())

    t = threading.Thread(target=body)
    t.start()
    t.join(timeout=10)
    assert seen["comm"] == "sw-a-name-longe"


# thread → the OS name it gives itself
THREADS = {
    "egress": (lambda i: i.dispatcher._egress_super._thread, "sw-egress"),
    "loop": (lambda i: i.dispatcher._thread, "sw-loop"),
    "seal-0": (lambda i: i.event_store.sealer._supervisors[0]._thread,
               "sw-seal-0"),
    "checkpoint": (lambda i: i.checkpointer._thread, "sw-checkpoint"),
    "presence": (lambda i: i.presence._thread, "sw-presence"),
    "outbound": (lambda i: i.outbound._workers["legs-client"]._thread,
                 "sw-out-legs-cli"),
}


@pytest.fixture(scope="module")
def running(tmp_path_factory):
    inst = _instance(tmp_path_factory.mktemp("names"))
    try:
        yield inst
    finally:
        inst.stop()


@linux
@pytest.mark.parametrize("thread", sorted(THREADS))
def test_a_long_lived_thread_carries_its_os_name(running, thread):
    find, name = THREADS[thread]
    t = find(running)
    assert t is not None and t.is_alive()
    assert wait_until(lambda: _comm(t.native_id) == name), _comm(t.native_id)
    assert os.path.exists(f"/proc/self/task/{t.native_id}")

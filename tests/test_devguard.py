"""Device-tier fault containment: breaker ladder, hung-step watchdog,
health-plane propagation, and the dispatcher's containment protocol.

Unit half: :mod:`sitewhere_tpu.runtime.devguard` under a fake clock —
distinct-batch strike counting, the chained → single-step →
cpu-fallback ladder, half-open probe semantics, soft/hard watchdog
budgets with parts-refcounted entries.  Integration half: a live
instance driven through the ``device.dispatch`` injection seam
(``runtime/faults.py``) — containment WITHOUT restart, poison-row
bisect to replayable dead letters, NaN quarantine via the packed
telemetry scalar, and the unhealthy flag riding the fleet heartbeat.
"""

import json
import time

import numpy as np
import pytest

from sitewhere_tpu.runtime import faults
from sitewhere_tpu.runtime.devguard import (
    CHAINED,
    FALLBACK,
    SINGLE_STEP,
    DeviceBreaker,
    DeviceWatchdog,
)


@pytest.fixture(autouse=True)
def _clean_device_faults():
    faults.device_clear()
    yield
    faults.device_clear()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# DeviceBreaker
# ---------------------------------------------------------------------------

class TestDeviceBreaker:
    def test_distinct_batches_trip_same_batch_does_not(self):
        clock = FakeClock()
        b = DeviceBreaker(threshold=3, clock=clock)
        # the bisect protocol re-faults ONE batch many times: one strike
        for _ in range(10):
            b.record_fault(seq=7)
        assert b.level == CHAINED and b.trips == 0
        b.record_fault(seq=8)
        assert b.level == CHAINED
        assert b.record_fault(seq=9)          # third DISTINCT batch
        assert b.level == SINGLE_STEP and b.trips == 1

    def test_strikes_age_out_of_the_window(self):
        clock = FakeClock()
        b = DeviceBreaker(threshold=2, window_s=60.0, clock=clock)
        b.record_fault(1)
        clock.advance(61.0)
        b.record_fault(2)                      # the first strike expired
        assert b.level == CHAINED
        b.record_fault(3)
        assert b.level == SINGLE_STEP

    def test_ladder_stops_at_fallback(self):
        clock = FakeClock()
        b = DeviceBreaker(threshold=1, clock=clock)
        b.record_fault(1)
        assert b.level == SINGLE_STEP
        b.record_fault(2)
        assert b.level == FALLBACK
        b.record_fault(3)
        assert b.level == FALLBACK             # no rung below fallback

    def test_cooldown_probe_then_chained_success_restores(self):
        clock = FakeClock()
        trips, restores = [], []
        b = DeviceBreaker(threshold=1, cooldown_s=30.0, clock=clock,
                          on_trip=trips.append,
                          on_restore=lambda: restores.append(True))
        b.record_fault(1)
        assert trips == [SINGLE_STEP]
        assert not b.allow_chain()             # cooling down
        clock.advance(31.0)
        assert b.allow_chain()                 # half-open probe admitted
        b.record_success(chained=True)
        assert b.level == CHAINED and restores == [True]
        assert b.allow_chain()

    def test_probe_failure_recloses_and_restarts_cooldown(self):
        clock = FakeClock()
        b = DeviceBreaker(threshold=1, cooldown_s=30.0, clock=clock)
        b.record_fault(1)
        clock.advance(31.0)
        assert b.allow_chain()                 # probing
        b.record_fault(2)                      # probe chain died
        assert b.level == FALLBACK             # and the strike escalated
        assert not b.allow_chain()
        clock.advance(29.0)
        assert not b.allow_chain()             # cooldown restarted
        clock.advance(2.0)
        assert b.allow_chain()

    def test_non_chained_success_does_not_restore(self):
        clock = FakeClock()
        b = DeviceBreaker(threshold=1, clock=clock)
        b.record_fault(1)
        b.record_success(chained=False)        # a single-step drain
        assert b.level == SINGLE_STEP

    def test_snapshot_shape(self):
        b = DeviceBreaker()
        snap = b.snapshot()
        assert snap["levelName"] == "chained"
        assert {"level", "strikes", "probing", "trips",
                "restores"} <= set(snap)


# ---------------------------------------------------------------------------
# DeviceWatchdog
# ---------------------------------------------------------------------------

class TestDeviceWatchdog:
    def test_soft_once_per_entry_hard_once_per_episode(self):
        clock = FakeClock()
        soft, hard = [], []
        wd = DeviceWatchdog(soft_s=1.0, hard_s=5.0, clock=clock,
                            on_soft=lambda r, e: soft.append((r, e)),
                            on_unhealthy=lambda r, e: hard.append((r, e)))
        token = wd.begin("plan-A")
        clock.advance(1.5)
        assert not wd.check()
        assert len(soft) == 1 and soft[0][0] == "plan-A"
        wd.check()
        assert len(soft) == 1                  # once per entry
        clock.advance(4.0)
        assert wd.check()                      # past hard: unhealthy
        assert len(hard) == 1 and wd.unhealthy
        wd.check()
        assert len(hard) == 1                  # once per episode
        wd.end(token)
        assert not wd.unhealthy                # self-clears on drain

    def test_parts_refcount_drains_on_last_end(self):
        clock = FakeClock()
        recovered = []
        wd = DeviceWatchdog(soft_s=1.0, hard_s=2.0, clock=clock,
                            on_recovered=lambda: recovered.append(True))
        token = wd.begin(["p1", "p2", "p3"], parts=3)
        clock.advance(3.0)
        assert wd.check() and wd.unhealthy
        wd.end(token)
        wd.end(token)
        assert wd.unhealthy                    # two of three parts done
        wd.end(token)
        assert not wd.unhealthy and recovered == [True]
        wd.end(token)                          # idempotent
        wd.end(None)                           # None-safe

    def test_opaque_records_hand_back_verbatim(self):
        clock = FakeClock()
        seen = []
        wd = DeviceWatchdog(soft_s=0.5, hard_s=9.0, clock=clock,
                            on_soft=lambda r, e: seen.append(r))
        payload = [object(), object()]
        wd.begin(payload, parts=2)
        clock.advance(1.0)
        wd.check()
        assert seen and seen[0] is payload     # no copy, no render

    def test_calibrate_floors_protect_cpu_hosts(self):
        wd = DeviceWatchdog()
        wd.calibrate(stage_ms=0.2)             # a fast chip
        assert wd.soft_s == pytest.approx(0.25)   # floored
        assert wd.hard_s == pytest.approx(2.0)    # floored
        wd.calibrate(stage_ms=30.0)            # a real TPU step
        assert wd.soft_s == pytest.approx(1.5)    # 50x stage
        assert wd.hard_s == pytest.approx(12.0)   # 400x stage

    def test_snapshot_tracks_oldest(self):
        clock = FakeClock()
        wd = DeviceWatchdog(clock=clock)
        wd.begin("x")
        clock.advance(2.0)
        snap = wd.snapshot()
        assert snap["inflight"] == 1
        assert snap["oldestS"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# health-plane propagation: the unhealthy flag rides the heartbeat
# ---------------------------------------------------------------------------

class TestDeviceUnhealthyPropagation:
    def _table(self):
        from sitewhere_tpu.rpc.health import PeerHealthTable

        clock = FakeClock()
        return PeerHealthTable([1], clock=clock), clock

    def test_unhealthy_peer_parks_drain_then_recovers(self):
        table, clock = self._table()
        table.observe_heartbeat(1, now=clock())
        assert table.can_drain(1)
        table.observe_heartbeat(1, device_unhealthy=True, now=clock())
        assert not table.can_drain(1)          # RPC alive, chip wedged
        assert table.snapshot()["1"]["device_unhealthy"] is True
        table.observe_heartbeat(1, device_unhealthy=False, now=clock())
        assert table.can_drain(1)

    def test_heartbeat_body_carries_the_dispatcher_flag(self, tmp_path):
        from sitewhere_tpu.rpc.forward import HostForwarder

        wedged = [False]
        fwd = HostForwarder(None, 0, {0: None},
                            data_dir=str(tmp_path / "spool"),
                            heartbeat_interval_s=0,
                            device_unhealthy=lambda: wedged[0])
        try:
            assert fwd.heartbeat_body(0)["deviceUnhealthy"] is False
            wedged[0] = True
            assert fwd.heartbeat_body(0)["deviceUnhealthy"] is True
        finally:
            fwd.stop()


# ---------------------------------------------------------------------------
# dispatcher integration: containment through the device seam
# ---------------------------------------------------------------------------

def _instance_config(tmp_path, **pipeline):
    from sitewhere_tpu.runtime.config import Config

    return Config({
        "instance": {"id": "devguard-inst",
                     "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 128,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1,
                     **pipeline},
        "overload": {"cooldown_s": 3600.0},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
    }, apply_env=False)


def _seed_devices(inst, n=4):
    inst.device_management.create_device_type(token="sensor", name="S")
    for i in range(n):
        inst.device_management.create_device(token=f"d-{i}",
                                             device_type="sensor")
        inst.device_management.create_device_assignment(device=f"d-{i}")


def _lines(values, ts0=1_754_600_000, token="d-0"):
    return "\n".join(json.dumps({
        "deviceToken": token, "type": "Measurement",
        "request": {"name": "temp", "value": v, "eventDate": ts0 + i},
    }) for i, v in enumerate(values)).encode()


class TestDispatcherContainment:
    def test_device_fault_contained_without_restart(self, tmp_path):
        """A transient device fault is contained IN PROCESS: the full-set
        retry re-dispatches from the last committed epoch, every row
        commits, and the journal offset advances — no restart, no
        replay, no dead letters."""
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_devices(inst)
            gen0 = inst.device_state.lease_generation
            faults.device_inject("device.dispatch", times=1)
            inst.dispatcher.ingest_wire_lines(_lines([1.0, 2.0, 3.0]))
            inst.dispatcher.flush()
            inst.event_store.flush()
            assert faults.device_fired("device.dispatch") == 1
            assert inst.event_store.total_events == 3
            # the gate reopened: the offset committed past the record
            assert inst.dispatcher.journal_reader.committed == 1
            c = inst.metrics.snapshot()["counters"]
            assert c.get("device.fault.step_faults", 0) == 1
            assert c.get("device.fault.poison_rows", 0) == 0
            assert inst.dead_letters.end_offset == 0
            # same live manager throughout (no restart, no re-build)
            assert inst.device_state.lease_generation >= gen0
        finally:
            inst.stop()
            inst.terminate()

    def test_poison_rows_bisect_to_replayable_dead_letters(self, tmp_path):
        """Only the poison rows leave the pipeline — isolated by bisect,
        dead-lettered with their raw columns, and replayable through
        ``requeue_dead_letter`` into the quarantine path."""
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path,
                                         quarantine_after=2))
        inst.start()
        try:
            _seed_devices(inst)
            faults.device_inject("device.dispatch", times=None,
                                 when_nonfinite=True)
            inst.dispatcher.ingest_wire_lines(
                _lines([1.0, float("nan"), 3.0, float("nan"), 5.0]))
            inst.dispatcher.flush()
            faults.device_clear()
            inst.event_store.flush()
            # the three clean rows committed; the two poison rows left
            assert inst.event_store.total_events == 3
            letters = [d for d in inst.list_dead_letters(limit=10)
                       if d.get("kind") == "device-poison"]
            assert sum(d["count"] for d in letters) == 2
            vals = [v for d in letters for v in d["columns"]["value"]]
            assert all(not np.isfinite(v) for v in vals)

            # replay: the rows re-enter, the device masks + counts them,
            # and the host attribution quarantines the offender
            for d in letters:
                res = inst.requeue_dead_letter(int(d["offset"]))
                assert res["requeued"] and res["kind"] == "device-poison"
            inst.dispatcher.flush()
            snap = inst.metrics.snapshot()
            assert snap["counters"].get(
                "pipeline.quarantine.rows_nonfinite", 0) == 2
            assert snap["gauges"].get(
                "pipeline.quarantine.devices", 0) == 1
            assert snap["counters"].get(
                "pipeline.quarantine.state_changes", 0) == 1
            df = inst.dispatcher.metrics_snapshot()["device_fault"]
            assert df["quarantined_devices"] == 1
        finally:
            inst.stop()
            inst.terminate()

    def test_watchdog_trips_and_recovers_on_live_instance(self, tmp_path):
        """A stalled dispatch trips soft then hard from the LOOP thread
        (the dispatch thread is the one wedged), and the tier recovers
        when the dispatch drains."""
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_devices(inst)
            inst.dispatcher.watchdog.soft_s = 0.03
            inst.dispatcher.watchdog.hard_s = 0.12
            faults.device_inject("device.dispatch", exc=None,
                                 stall_s=0.4)
            inst.dispatcher.ingest_wire_lines(_lines([1.0]))
            inst.dispatcher.flush()
            wd = inst.dispatcher.watchdog.snapshot()
            assert wd["softTrips"] >= 1 and wd["hardTrips"] >= 1
            assert not wd["unhealthy"]         # self-cleared on drain
            assert not inst.dispatcher.device_unhealthy
            c = inst.metrics.snapshot()["counters"]
            assert c.get("device.fault.watchdog_soft_trips", 0) >= 1
            assert c.get("device.fault.watchdog_hard_trips", 0) >= 1
            # zero loss: the stalled rows still landed
            inst.event_store.flush()
            assert inst.event_store.total_events == 1
        finally:
            inst.stop()
            inst.terminate()

    def test_loop_idle_tick_never_waits_for_the_step_lock(self, tmp_path):
        """The loop thread is the watchdog's only observer, so its idle
        housekeeping (inline drain, offset commit) may try the step lock
        but never wait for it: a dispatch that takes the lock AFTER the
        tick's probe — here, while the loop thread sits in its deadline
        poll — may be the wedged one.  The race is forced, not hoped
        for: the loop is parked inside ``_take``, the lock is taken,
        the loop released; it must keep checking while the lock is held."""
        import threading

        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            d = inst.dispatcher
            assert not d._offloaded()   # CPU default: the drain is inline
            parked, go = threading.Event(), threading.Event()
            checks = []
            take, check = d._take, d.watchdog.check

            def gated_take(intake):
                if (threading.current_thread() is d._thread
                        and not parked.is_set()):
                    parked.set()
                    assert go.wait(5)
                return take(intake)

            def counted_check(*a, **kw):
                checks.append(time.monotonic())
                return check(*a, **kw)

            d._take, d.watchdog.check = gated_take, counted_check
            assert parked.wait(5)
            with d._step_lock:          # the "wedged dispatch"
                go.set()
                t0 = time.monotonic()
                time.sleep(0.25)
                seen = sum(t >= t0 for t in checks)
            assert seen >= 5, f"loop thread made {seen} checks in 0.25 s"
        finally:
            inst.stop()
            inst.terminate()

    def test_breaker_trip_rides_and_releases_the_overload_ladder(
            self, tmp_path):
        """The breaker trip forces DEGRADED with its own driver tag; the
        restore releases ONLY its own demotion."""
        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.runtime.overload import OverloadState

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_devices(inst)
            d = inst.dispatcher
            d.breaker.cooldown_s = 3600.0
            for seq in range(d.breaker.threshold):
                faults.device_inject("device.dispatch", times=1)
                d.ingest_wire_lines(_lines([float(seq)],
                                           ts0=1_754_700_000 + 10 * seq))
                d.flush()
                faults.device_clear()
            assert d.breaker.level == SINGLE_STEP
            assert inst.overload.state == OverloadState.DEGRADED
            assert inst.overload.last_driver == "device-breaker"
            # restore via the breaker's own path releases the force
            d.breaker.record_success(chained=True)
            assert d.breaker.level == CHAINED
            assert inst.overload.state == OverloadState.NORMAL
        finally:
            inst.stop()
            inst.terminate()

"""Device-state manager + presence detection.

Reference behaviors covered: last-known-state merge visibility through the
query surface (DeviceStateImpl RPC analogs), presence sweep marking
overdue devices (DevicePresenceManager), send-once notification semantics,
and re-arming when a device comes back.
"""

import jax
import numpy as np
import pytest

from sitewhere_tpu.ids import IdentityMap, NULL_ID
from sitewhere_tpu.pipeline import pipeline_step
from sitewhere_tpu.schema import DeviceState, EventType, RuleTable, ZoneTable
from sitewhere_tpu.services.common import EntityNotFound
from sitewhere_tpu.state import DeviceStateManager, PresenceManager, presence_sweep

from helpers import make_batch, make_registry, measurement, location


CAP = 64


@pytest.fixture
def identity():
    im = IdentityMap(capacity=CAP)
    for i in range(8):
        assert im.device.mint(f"dev-{i}") == i
    return im


@pytest.fixture
def manager(identity):
    return DeviceStateManager(CAP, identity)


def run_step(manager, rows):
    registry = make_registry(capacity=CAP, n_devices=8)
    rules = RuleTable.empty(4)
    zones = ZoneTable.empty(4)
    new_state, out = pipeline_step(
        registry, manager.current, rules, zones, make_batch(rows)
    )
    manager.commit(new_state)
    return out


class TestStateManager:
    def test_merge_visible_through_queries(self, manager):
        run_step(
            manager,
            [
                measurement(0, mtype=1, value=42.5, ts=5000),
                location(3, lat=10.0, lon=20.0, ts=6000),
            ],
        )
        s0 = manager.get_device_state("dev-0")
        assert s0["last_event_type"] == EventType.MEASUREMENT
        assert s0["last_event_ts_s"] == 5000
        assert s0["last_values"][1] == 42.5
        s3 = manager.get_device_state("dev-3")
        assert s3["last_location"]["lat"] == 10.0
        assert s3["last_location"]["lon"] == 20.0
        # Device with no events yet.
        assert manager.get_device_state("dev-7")["last_event_type"] is None

    def test_unknown_device(self, manager):
        with pytest.raises(EntityNotFound):
            manager.get_device_state("nope")

    def test_seen_since_and_summary(self, manager):
        run_step(manager, [measurement(0, ts=1000), measurement(1, ts=9000)])
        assert manager.seen_since(5000) == [1]
        assert manager.summary()["devices_with_state"] == 2


class TestPresenceSweep:
    def test_overdue_devices_marked(self, manager):
        run_step(manager, [measurement(0, ts=1000), measurement(1, ts=50_000)])
        batch = manager.apply_presence_sweep(now_s=60_000, missing_after_s=30_000)
        # dev-0 is 59k stale (> 30k) → missing; dev-1 is 10k stale → present.
        assert manager.missing_device_ids() == [0]
        assert batch is not None
        ids = batch["device_id"]
        assert list(ids) == [0]
        assert int(batch["event_type"][0]) == EventType.STATE_CHANGE
        # the report is host columns: no program is built at its length
        assert all(type(col) is np.ndarray for col in batch.values())
        assert not batch["update_state"].any()

    def test_devices_without_events_ignored(self, manager):
        batch = manager.apply_presence_sweep(now_s=10**9, missing_after_s=1)
        assert batch is None
        assert manager.missing_device_ids() == []

    def test_send_once(self, manager):
        run_step(manager, [measurement(0, ts=1000)])
        assert manager.apply_presence_sweep(50_000, 30_000) is not None
        # Second sweep: still missing, but not NEWLY missing → no batch.
        assert manager.apply_presence_sweep(60_000, 30_000) is None

    def test_rearm_on_return(self, manager):
        run_step(manager, [measurement(0, ts=1000)])
        manager.apply_presence_sweep(50_000, 30_000)
        assert manager.missing_device_ids() == [0]
        # Device comes back: pipeline step clears the flag...
        run_step(manager, [measurement(0, ts=55_000)])
        assert manager.missing_device_ids() == []
        # ...and a later lapse notifies again.
        assert manager.apply_presence_sweep(100_000, 30_000) is not None


class TestPresenceManager:
    def test_sweep_once_and_counters(self, manager):
        run_step(manager, [measurement(0, ts=1000)])
        emitted = []
        pm = PresenceManager(
            manager,
            missing_after_s=30_000,
            on_state_changes=emitted.append,
            clock=lambda: 50_000,
        )
        assert pm.sweep_once() == 1
        assert pm.total_marked_missing == 1
        assert len(emitted) == 1
        assert pm.sweep_once() == 0  # send-once

    def test_background_thread(self, manager):
        import time as _time

        run_step(manager, [measurement(0, ts=1000)])
        pm = PresenceManager(
            manager,
            check_interval_s=0.02,
            missing_after_s=30_000,
            clock=lambda: 50_000,
        )
        pm.start()
        deadline = _time.time() + 2
        while pm.sweeps == 0 and _time.time() < deadline:
            _time.sleep(0.01)
        pm.stop()
        assert pm.sweeps >= 1
        assert manager.missing_device_ids() == [0]

    def test_tenant_ids_in_state_changes(self, identity):
        tenants = np.full(CAP, 3, np.int32)
        mgr = DeviceStateManager(
            CAP, identity, tenant_id_of_device=lambda ids: tenants[ids]
        )
        run_step(mgr, [measurement(0, ts=1000, tenant=0)])
        # run_step's registry uses tenant 0; the emission callback uses the
        # injected mapping (tenant 3) — verifying the hook is honored.
        batch = mgr.apply_presence_sweep(50_000, 30_000)
        assert int(batch["tenant_id"][0]) == 3


def test_presence_sweep_is_jittable_and_pure():
    import jax.numpy as jnp

    state = DeviceState.empty(16)
    state = state.replace(
        last_event_type=state.last_event_type.at[2].set(EventType.MEASUREMENT),
        last_event_ts_s=state.last_event_ts_s.at[2].set(100),
    )
    new_state, newly = presence_sweep(state, jnp.int32(10_000), jnp.int32(500))
    assert bool(newly[2]) and not bool(newly[0])
    # Input untouched (functional update).
    assert not bool(state.presence_missing[2])
    assert bool(new_state.presence_missing[2])


class TestCommitMergeRace:
    def test_concurrent_sweep_flags_survive_commit(self, manager):
        """A sweep that lands between the dispatcher's state read and its
        commit must not be clobbered (lost-update race): flags for devices
        the batch did not touch are preserved when the batch is passed."""
        # dev-0 and dev-5 have old events
        run_step(manager, [measurement(0, ts=1000), measurement(5, ts=1000)])
        base = manager.current  # dispatcher snapshot S0

        # slow pipeline step computes from S0...
        registry = make_registry(capacity=CAP, n_devices=8)
        batch = make_batch([measurement(0, ts=90_000)])
        new_state, out = pipeline_step(
            registry, base, RuleTable.empty(4), ZoneTable.empty(4), batch
        )

        # ...meanwhile the presence sweep marks both 0 and 5 missing
        swept = manager.apply_presence_sweep(now_s=80_000, missing_after_s=10_000)
        assert sorted(manager.missing_device_ids()) == [0, 5]
        assert swept is not None

        # dispatcher commits: dev-0 (touched, fresh event) cleared;
        # dev-5 (untouched) keeps the sweep's flag
        manager.commit(new_state, batch=batch, accepted=out.accepted)
        assert manager.missing_device_ids() == [5]
        # and the next sweep does NOT re-mark dev-5 (send-once holds)
        assert manager.apply_presence_sweep(80_000, 10_000) is None

    def test_rejected_rows_do_not_clear_sweep_flags(self, manager):
        """A batch row the step REJECTED (e.g. unregistered device id) must
        not count as touched — its sweep flag survives the commit."""
        run_step(manager, [measurement(0, ts=1000), measurement(5, ts=1000)])
        base = manager.current

        registry = make_registry(capacity=CAP, n_devices=8)
        # row for dev-5 arrives but its registry slot is inactive → rejected
        import numpy as np

        from sitewhere_tpu.schema import AssignmentStatus

        registry = registry.replace(
            active=registry.active.at[5].set(False)
        )
        batch = make_batch([measurement(0, ts=90_000), measurement(5, ts=90_000)])
        new_state, out = pipeline_step(
            registry, base, RuleTable.empty(4), ZoneTable.empty(4), batch
        )
        assert not bool(np.asarray(out.accepted)[1])

        manager.apply_presence_sweep(now_s=80_000, missing_after_s=10_000)
        assert sorted(manager.missing_device_ids()) == [0, 5]

        manager.commit(new_state, batch=batch, accepted=out.accepted)
        # dev-0 cleared (accepted fresh event); dev-5's flag survives even
        # though a (rejected) row named it
        assert manager.missing_device_ids() == [5]

    def test_present_now_commit_path_matches_batch_path(self, manager):
        """The dispatcher's hot path passes the step's present_now output
        instead of re-deriving touched rows from the batch; both forms
        must reconcile a concurrent sweep identically."""
        import numpy as np

        run_step(manager, [measurement(0, ts=1000), measurement(5, ts=1000)])
        base = manager.current
        registry = make_registry(capacity=CAP, n_devices=8)
        batch = make_batch([measurement(0, ts=90_000)])
        new_state, out = pipeline_step(
            registry, base, RuleTable.empty(4), ZoneTable.empty(4), batch
        )
        # present_now marks exactly the merged device
        pn = np.asarray(out.present_now)
        assert pn[0] and not pn[5] and pn.sum() == 1

        manager.apply_presence_sweep(now_s=80_000, missing_after_s=10_000)
        assert sorted(manager.missing_device_ids()) == [0, 5]
        manager.commit(new_state, present_now=out.present_now)
        # dev-0 (merged) cleared; dev-5 (untouched) keeps the sweep flag —
        # identical to the batch/accepted re-derive form above
        assert manager.missing_device_ids() == [5]


class TestLeasePacked:
    """The donated-chain hand-off (``lease_packed`` → chain →
    ``commit_packed(lease_token=...)``) — the dispatcher ring's
    production path wherever donation is real (TPU).  Donation is a
    no-op on CPU, but the token protocol, the reader-safety twin
    materialization, and the sweep-intervened merge all run fully."""

    def _packed_step(self, manager_ps, rows):
        import jax

        from sitewhere_tpu.pipeline.packed import (
            BATCH_F,
            BATCH_I,
            pack_batch_host,
            pack_tables,
            packed_pipeline_step,
        )
        from sitewhere_tpu.schema import as_numpy

        registry = make_registry(capacity=CAP, n_devices=8)
        tables = pack_tables(registry, RuleTable.empty(4), ZoneTable.empty(4))
        host = as_numpy(make_batch(rows))
        cols = {f: np.asarray(getattr(host, f)) for f in BATCH_I + BATCH_F}
        bi, bf = pack_batch_host(cols, len(rows))
        return jax.jit(packed_pipeline_step)(tables, manager_ps, bi, bf)

    def test_fast_path_and_reader_survives_donation(self, manager):
        run_step(manager, [measurement(0, ts=1000)])
        ps, token = manager.lease_packed()
        new_ps, _oi, _mets, present = self._packed_step(
            ps, [measurement(0, ts=5000)])
        # simulate the donation: the chain consumed the leased buffers
        for leaf in jax.tree.leaves(ps):
            leaf.delete()
        # a reader arriving mid-chain sees the pre-chain epoch from the
        # materialized twin — never the deleted/donated buffers
        assert manager.get_device_state("dev-0")["last_event_ts_s"] == 1000
        manager.commit_packed(new_ps, present_now=present,
                              lease_token=token)
        assert manager.get_device_state("dev-0")["last_event_ts_s"] == 5000

    def test_sweep_during_lease_merges_at_commit(self, manager):
        """A presence sweep landing mid-chain invalidates the lease
        token: the commit must re-apply the sweep's flags for devices
        the chain did not merge (same lost-update rule as the unpacked
        commit race)."""
        run_step(manager, [measurement(0, ts=1000), measurement(5, ts=1000)])
        ps, token = manager.lease_packed()
        new_ps, _oi, _mets, present = self._packed_step(
            ps, [measurement(0, ts=90_000)])
        swept = manager.apply_presence_sweep(
            now_s=80_000, missing_after_s=10_000)
        assert swept is not None
        assert sorted(manager.missing_device_ids()) == [0, 5]
        manager.commit_packed(new_ps, present_now=present,
                              lease_token=token)
        # dev-0 (chain-merged, fresh event) cleared; dev-5 keeps the flag
        assert manager.missing_device_ids() == [5]


def test_update_state_false_rows_do_not_touch_state(manager):
    """System-generated events (presence STATE_CHANGEs, derived alerts)
    carry update_state=False: persisted/fanned out but never merged —
    reference IDeviceEvent.isUpdateState() semantics."""
    import jax.numpy as jnp

    run_step(manager, [measurement(0, ts=1000)])
    manager.apply_presence_sweep(now_s=80_000, missing_after_s=10_000)
    assert manager.missing_device_ids() == [0]

    registry = make_registry(capacity=CAP, n_devices=8)
    batch = make_batch([
        dict(device_id=0, tenant_id=0, event_type=EventType.STATE_CHANGE,
             ts_s=80_000, update_state=False),
    ])
    base = manager.current
    new_state, out = pipeline_step(
        registry, base, RuleTable.empty(4), ZoneTable.empty(4), batch
    )
    manager.commit(new_state, batch=batch, accepted=out.accepted)
    # still missing, last_event_ts unchanged — the STATE_CHANGE about the
    # device did not make it look alive
    assert manager.missing_device_ids() == [0]
    assert manager.get_device_state("dev-0")["last_event_ts_s"] == 1000

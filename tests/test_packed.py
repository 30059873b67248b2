"""Bit-exactness of the packed step interface vs the unpacked step.

The packed form (``pipeline/packed.py``) is a pure interface transform —
same :func:`pipeline_step` inside — so every output and the full state
carry must match the unpacked step exactly, not approximately.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.ops.geo import pad_polygon
from sitewhere_tpu.pipeline import pipeline_step
from sitewhere_tpu.pipeline.packed import (
    BATCH_F,
    BATCH_I,
    TENANT_METER_COUNTERS,
    TENANT_METER_SLOTS,
    PackedView,
    pack_batch_host,
    pack_state,
    pack_tables,
    packed_pipeline_step,
    unpack_batch,
    unpack_state,
    unpack_tables,
)
from sitewhere_tpu.schema import (
    AssignmentStatus,
    DeviceState,
    EventBatch,
    EventType,
    Registry,
    RuleKind,
    RuleTable,
    ZoneTable,
    as_numpy,
)


def _tables(cap=256, n_active=180, n_tenants=2):
    idx = jnp.arange(cap)
    on = idx < n_active
    registry = Registry.empty(cap).replace(
        active=on,
        tenant_id=jnp.where(on, idx % n_tenants, -1),
        device_type_id=jnp.where(on, idx % 3, -1),
        assignment_id=jnp.where(on, idx, -1),
        assignment_status=jnp.where(
            idx < n_active - 20, AssignmentStatus.ACTIVE, 0),
        area_id=jnp.where(on, idx % 5, -1),
        customer_id=jnp.where(on, 2, -1),
        asset_id=jnp.where(on, 3, -1),
    )
    rules = RuleTable.empty(8)
    rules = rules.replace(
        active=rules.active.at[0].set(True).at[1].set(True),
        mtype_id=rules.mtype_id.at[0].set(0),
        op=rules.op.at[0].set(0),
        threshold=rules.threshold.at[0].set(50.0).at[1].set(10.0),
        alert_code=rules.alert_code.at[0].set(7).at[1].set(8),
        kind=rules.kind.at[1].set(RuleKind.WINDOW_MEAN),
        window_idx=rules.window_idx.at[1].set(1),
    )
    zones = ZoneTable.empty(4, max_verts=8)
    padded = pad_polygon([[0.0, 0.0], [10.0, 0.0], [10.0, 10.0], [0.0, 10.0]], 8)
    zones = zones.replace(
        active=zones.active.at[0].set(True),
        verts=zones.verts.at[0].set(jnp.asarray(padded)),
        nvert=zones.nvert.at[0].set(4),
        alert_code=zones.alert_code.at[0].set(9),
    )
    return registry, rules, zones


def _batch(width=512, cap=256, n_tenants=2, seed=0):
    rng = np.random.default_rng(seed)
    device_id = rng.integers(-2, cap + 10, width).astype(np.int32)
    cols = dict(
        valid=(rng.random(width) < 0.9),
        device_id=device_id,
        tenant_id=(device_id % n_tenants).astype(np.int32),
        event_type=rng.integers(0, 4, width).astype(np.int32),
        ts_s=rng.integers(1_000, 2_000, width).astype(np.int32),
        ts_ns=rng.integers(0, 1_000_000_000, width).astype(np.int32),
        mtype_id=rng.integers(-1, 4, width).astype(np.int32),
        value=rng.uniform(0, 100, width).astype(np.float32),
        lat=rng.uniform(-20, 20, width).astype(np.float32),
        lon=rng.uniform(-20, 20, width).astype(np.float32),
        elevation=np.zeros(width, np.float32),
        alert_code=np.where(rng.random(width) < 0.1, 3, NULL_ID).astype(np.int32),
        alert_level=rng.integers(0, 3, width).astype(np.int32),
        command_id=np.full(width, NULL_ID, np.int32),
        payload_ref=np.arange(width, dtype=np.int32),
        update_state=(rng.random(width) < 0.95),
    )
    return cols


def _seeded_state(cap=256, M=4, K=3, seed=1):
    rng = np.random.default_rng(seed)
    s = DeviceState.empty(cap, M, K)
    return s.replace(
        last_event_ts_s=jnp.asarray(rng.integers(0, 1_500, cap), jnp.int32),
        last_values=jnp.asarray(rng.uniform(0, 50, (cap, M)), jnp.float32),
        last_value_ts_s=jnp.asarray(rng.integers(0, 1_500, (cap, M)), jnp.int32),
        ewma_values=jnp.asarray(rng.uniform(0, 50, (cap, M, K)), jnp.float32),
        presence_missing=jnp.asarray(rng.random(cap) < 0.2),
    )


def test_pack_unpack_roundtrip():
    registry, rules, zones = _tables()
    state = _seeded_state()
    cols = _batch()
    t = pack_tables(registry, rules, zones)
    r2, ru2, z2 = unpack_tables(t)
    for a, b in zip(jax.tree.leaves(registry.replace(epoch=jnp.int32(0))),
                    jax.tree.leaves(r2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(rules), jax.tree.leaves(ru2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(zones), jax.tree.leaves(z2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ps = pack_state(state)
    s2 = unpack_state(ps)
    for f in state.__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(state, f)), np.asarray(getattr(s2, f)), err_msg=f)

    bi, bf = pack_batch_host(cols, width=len(cols["device_id"]))
    b2 = unpack_batch(jnp.asarray(bi), jnp.asarray(bf))
    for f in BATCH_I + BATCH_F:
        np.testing.assert_array_equal(
            np.asarray(cols[f]), np.asarray(getattr(b2, f)), err_msg=f)


def test_packed_step_bit_exact():
    registry, rules, zones = _tables()
    state = _seeded_state()
    cols = _batch()
    width = len(cols["device_id"])
    batch = EventBatch(**{k: jnp.asarray(v) for k, v in cols.items()})

    ref_state, ref_out = jax.jit(pipeline_step)(
        registry, state, rules, zones, batch)

    t = pack_tables(registry, rules, zones)
    ps = pack_state(state)
    bi, bf = pack_batch_host(cols, width)
    step = jax.jit(packed_pipeline_step, donate_argnums=(1,))
    ps2, oi, metrics, present = step(t, ps, jnp.asarray(bi), jnp.asarray(bf))

    got_state = unpack_state(ps2)
    for f in ref_state.__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref_state, f)),
            np.asarray(getattr(got_state, f)), err_msg=f)

    view = PackedView(oi, metrics, present)
    ref = as_numpy(ref_out)
    np.testing.assert_array_equal(np.asarray(ref.accepted), view.accepted)
    np.testing.assert_array_equal(np.asarray(ref.unregistered), view.unregistered)
    np.testing.assert_array_equal(np.asarray(ref.unassigned), view.unassigned)
    for f in ("device_type_id", "assignment_id", "area_id", "customer_id",
              "asset_id", "rule_id", "zone_id"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), getattr(view, f), err_msg=f)
    np.testing.assert_array_equal(
        np.asarray(ref.present_now), np.asarray(view.present_now))
    m = view.metrics
    for f in ("processed", "accepted", "unregistered", "unassigned",
              "threshold_alerts", "zone_alerts"):
        assert int(getattr(ref.metrics, f)) == int(getattr(m, f)), f
    np.testing.assert_array_equal(np.asarray(ref.metrics.by_type), m.by_type)

    # the on-device occupancy telemetry block (rides the same metrics
    # vector) matches the unpacked reference outputs exactly
    tel = view.telemetry
    assert tel["rows_invalid"] == width - int(ref.metrics.processed)
    assert tel["state_writes"] == int(
        (np.asarray(ref.accepted)
         & np.asarray(batch.update_state)).sum())
    assert tel["presence_merges"] == int(np.asarray(ref.present_now).sum())
    assert tel["rows_nonfinite"] == int(np.asarray(ref.nonfinite).sum())

    # the per-tenant meter block matches a numpy segment-sum of the
    # reference outputs bucketed by tenant_id % TENANT_METER_SLOTS
    tm = view.tenant_meter
    assert tm is not None
    assert tm.shape == (len(TENANT_METER_COUNTERS), TENANT_METER_SLOTS)
    buckets = cols["tenant_id"].astype(np.int64) % TENANT_METER_SLOTS
    accepted = np.asarray(ref.accepted).astype(np.int64)
    writes = accepted & cols["update_state"]
    nonfinite = np.asarray(ref.nonfinite).astype(np.int64)
    for ci, per_row in enumerate((accepted, writes, nonfinite)):
        expect = np.bincount(buckets, weights=per_row,
                             minlength=TENANT_METER_SLOTS)
        np.testing.assert_array_equal(
            tm[ci], expect.astype(tm.dtype),
            err_msg=TENANT_METER_COUNTERS[ci])

    # derived alerts reconstruct from host cols + packed outputs
    np.testing.assert_array_equal(
        np.asarray(ref.derived_alerts.valid), view.derived_valid)
    rows = np.nonzero(view.derived_valid)[0]
    if rows.size:
        dcols = view.derived_cols(cols, rows)
        np.testing.assert_array_equal(
            dcols["alert_code"], np.asarray(ref.derived_alerts.alert_code)[rows])
        np.testing.assert_array_equal(
            dcols["alert_level"], np.asarray(ref.derived_alerts.alert_level)[rows])
        np.testing.assert_array_equal(
            dcols["device_id"], np.asarray(ref.derived_alerts.device_id)[rows])
        assert (dcols["event_type"] == int(EventType.ALERT)).all()
        assert not dcols["update_state"].any()


def test_packed_nonfinite_guard_bit_exact():
    """NaN/Inf rows are masked out of state/analytics ON DEVICE, counted
    per device in ``nonfinite_count``, and surfaced as the
    ``rows_nonfinite`` telemetry scalar on the SAME packed metrics
    vector — bit-exact against the unpacked step."""
    registry, rules, zones = _tables()
    state = _seeded_state()
    cols = _batch(seed=7)
    width = len(cols["device_id"])
    # poison a handful of KNOWN-valid, registered rows
    bad = [i for i in range(width)
           if cols["valid"][i] and 0 <= cols["device_id"][i] < 180][:5]
    cols["value"][bad[0]] = np.nan
    cols["value"][bad[1]] = np.inf
    cols["lat"][bad[2]] = np.nan
    cols["lon"][bad[3]] = -np.inf
    cols["elevation"][bad[4]] = np.nan
    batch = EventBatch(**{k: jnp.asarray(v) for k, v in cols.items()})

    ref_state, ref_out = jax.jit(pipeline_step)(
        registry, state, rules, zones, batch)

    t = pack_tables(registry, rules, zones)
    ps = pack_state(state)
    bi, bf = pack_batch_host(cols, width)
    ps2, oi, metrics, present = jax.jit(packed_pipeline_step)(
        t, ps, jnp.asarray(bi), jnp.asarray(bf))
    view = PackedView(oi, metrics, present)

    nonfinite = np.asarray(ref_out.nonfinite)
    assert nonfinite.sum() >= len(bad)   # the injected rows all flagged
    assert view.telemetry["rows_nonfinite"] == int(nonfinite.sum())

    got = unpack_state(ps2)
    for f in ref_state.__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref_state, f)),
            np.asarray(getattr(got, f)), err_msg=f)
    # the poisoned devices took a strike, not a state write
    nf_count = np.asarray(got.nonfinite_count)
    for i in (bad[0], bad[1]):
        dev = int(cols["device_id"][i])
        assert nf_count[dev] >= 1
        np.testing.assert_array_equal(
            np.asarray(got.last_values[dev]),
            np.asarray(state.last_values[dev]))


def test_packed_chain_donation():
    """The donated state carry survives a multi-step chain."""
    registry, rules, zones = _tables()
    state = _seeded_state()
    t = pack_tables(registry, rules, zones)
    ps = pack_state(state)
    step = jax.jit(packed_pipeline_step, donate_argnums=(1,))
    ref = state
    for seed in range(3):
        cols = _batch(seed=seed)
        width = len(cols["device_id"])
        bi, bf = pack_batch_host(cols, width)
        batch = EventBatch(**{k: jnp.asarray(v) for k, v in cols.items()})
        ref, _ = jax.jit(pipeline_step)(registry, ref, rules, zones, batch)
        ps, *_ = step(t, ps, jnp.asarray(bi), jnp.asarray(bf))
    got = unpack_state(ps)
    for f in ref.__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), np.asarray(getattr(got, f)), err_msg=f)


# -- the width ladder (PR 33): the same rows at every rung ----------------

RUNG_W = 8192   # the smallest width with four distinct rungs


def _rung_plans(n_rows, seeds):
    """One plan a seed out of a real single-shard batcher of
    width ``RUNG_W``, each of ``n_rows`` rows (so each at its rung), and
    the same rows laid into buffers of the configured width."""
    from sitewhere_tpu.ingest.batcher import Batcher

    b = Batcher(width=RUNG_W, n_shards=1, registry_capacity=256,
                resolve_device=lambda t: NULL_ID, resolve_mtype=lambda n: 0,
                resolve_alert=lambda n: 0, deadline_ms=5.0,
                clock=lambda: 0.0)
    out = []
    for seed in seeds:
        cols = _batch(width=n_rows, seed=seed)
        cols.pop("valid")   # the batcher owns it: every queued row is valid
        # a full width goes out as it fills: the top rung's own case
        (plan,) = b.add_arrays(**cols) or [b.flush()]
        assert plan.n_events == n_rows
        wide_i, wide_f, _ = b._assemble_buffers(RUNG_W)
        wide_i[BATCH_I.index("valid")] = 0
        wide_i[BATCH_I.index("update_state")] = 1   # its padding fill
        wide_i[:, :n_rows] = plan.packed_i[:, :n_rows]
        wide_f[:, :n_rows] = plan.packed_f[:, :n_rows]
        out.append((plan, wide_i, wide_f))
    return out


def _rung_row_counts():
    from sitewhere_tpu.ingest.batcher import plan_rungs

    return [(rung, n) for rung in plan_rungs(RUNG_W)
            for n in (rung, rung - 3)]


@pytest.mark.parametrize("rung, n_rows", _rung_row_counts())
def test_rows_step_the_same_at_their_rung_and_at_the_configured_width(
        rung, n_rows):
    """Rows past ``n_events`` are invalid at any width, and an invalid
    row touches no state, fires no rule and counts nowhere: two steps
    deep (the second reads what the first wrote), the narrow program
    gives the wide one's carry, outputs on the plan's rows, metrics,
    tenant block and ``present_now``, bit for bit."""
    from sitewhere_tpu.pipeline.packed import METRIC_SCALARS, TELEMETRY_SCALARS
    from sitewhere_tpu.pipeline.step import NUM_EVENT_TYPES

    registry, rules, zones = _tables()
    t = pack_tables(registry, rules, zones)
    step = jax.jit(packed_pipeline_step)
    narrow = wide = pack_state(_seeded_state())
    invalid_at = (len(METRIC_SCALARS) + NUM_EVENT_TYPES
                  + TELEMETRY_SCALARS.index("rows_invalid"))
    for plan, wide_i, wide_f in _rung_plans(n_rows, seeds=(11, 12)):
        assert plan.width == rung and plan.packed_i.shape[1] == rung
        narrow, oi_n, m_n, present_n = step(
            t, narrow, jnp.asarray(plan.packed_i), jnp.asarray(plan.packed_f))
        wide, oi_w, m_w, present_w = step(
            t, wide, jnp.asarray(wide_i), jnp.asarray(wide_f))
        np.testing.assert_array_equal(
            np.asarray(narrow.rows), np.asarray(wide.rows))
        oi_n, oi_w = np.asarray(oi_n), np.asarray(oi_w)
        np.testing.assert_array_equal(oi_n[:, :n_rows], oi_w[:, :n_rows])
        assert not oi_n[0, n_rows:].any() and not oi_w[0, n_rows:].any()
        # the one entry that counts the padding: width - processed
        m_n, m_w = np.asarray(m_n).copy(), np.asarray(m_w).copy()
        assert m_n[invalid_at] - rung == m_w[invalid_at] - RUNG_W
        m_n[invalid_at] = m_w[invalid_at] = 0
        np.testing.assert_array_equal(m_n, m_w)
        vn = PackedView(oi_n, m_n, present_n)
        vw = PackedView(oi_w, m_w, present_w)
        assert int(vn.metrics.processed) > 0.8 * n_rows
        np.testing.assert_array_equal(vn.tenant_meter, vw.tenant_meter)
        np.testing.assert_array_equal(
            np.asarray(present_n), np.asarray(present_w))
        assert np.asarray(present_n).any()


class TestPackedDispatcher:
    """The Instance dispatcher driving the packed step end-to-end.

    The key dispatcher flows: persistence+state, derived-alert
    re-injection (PackedView's host-side reconstruction), and
    auto-registration replay.
    """

    @pytest.fixture()
    def instance(self, tmp_path):
        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.runtime.config import Config

        cfg = Config({
            "instance": {"id": "packed-test",
                         "data_dir": str(tmp_path / "data")},
            "pipeline": {"width": 64, "registry_capacity": 1024,
                         "mtype_slots": 4, "deadline_ms": 5.0,
                         "n_shards": 1},
            "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        }, apply_env=False)
        inst = Instance(cfg)
        inst.start()
        yield inst
        inst.stop()
        inst.terminate()

    def _seed(self, inst, token="dev-1"):
        inst.device_management.create_device_type(token="sensor", name="Sensor")
        inst.device_management.create_device(token=token, device_type="sensor")
        inst.device_management.create_device_assignment(device=token)

    def test_ingest_to_store_and_state(self, instance):
        from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind

        self._seed(instance)
        for i in range(10):
            instance.dispatcher.ingest(DecodedRequest(
                kind=RequestKind.MEASUREMENT, device_token="dev-1",
                ts_s=1000 + i, mtype="temp", value=20.0 + i))
        instance.dispatcher.flush()
        snap = instance.dispatcher.metrics_snapshot()
        assert snap["processed"] == 10
        assert snap["accepted"] == 10
        state = instance.device_state.get_device_state("dev-1")
        assert state["last_event_ts_s"] == 1009
        instance.event_store.flush()
        assert instance.event_store.total_events == 10

    def test_derived_alert_via_packed_view(self, instance):
        from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind
        from sitewhere_tpu.schema import ComparisonOp

        self._seed(instance)
        instance.rules.create_rule(
            mtype="temp", op=ComparisonOp.GT, threshold=90.0,
            alert_type="overheat")
        instance.dispatcher.ingest(DecodedRequest(
            kind=RequestKind.MEASUREMENT, device_token="dev-1",
            ts_s=2000, mtype="temp", value=95.0))
        instance.dispatcher.flush()
        instance.dispatcher.flush()
        snap = instance.dispatcher.metrics_snapshot()
        assert snap["threshold_alerts"] == 1
        assert snap["derived_alerts"] == 1
        instance.event_store.flush()
        alerts = instance.event_store.query(event_type=int(EventType.ALERT))
        assert alerts.total == 1

    def test_auto_registration_and_replay(self, instance):
        import json as _json

        from sitewhere_tpu.ingest.decoders import JsonDecoder

        instance.registration.default_device_type = "sensor"
        instance.device_management.create_device_type(
            token="sensor", name="Sensor")
        payload = _json.dumps({
            "deviceToken": "ghost-1", "type": "measurement",
            "request": {"name": "temp", "value": 7.0, "ts": 3000},
        }).encode()
        req = JsonDecoder()(payload)[0]
        instance.dispatcher.ingest(req, payload)
        instance.dispatcher.flush()
        instance.dispatcher.flush()
        snap = instance.dispatcher.metrics_snapshot()
        assert snap["unregistered"] == 1
        assert snap["replayed"] == 1
        assert snap["accepted"] == 1
        assert instance.device_management.get_device("ghost-1") is not None

    def test_presence_sweep_interleaves(self, instance):
        """A sweep between steps must not be lost by commit_packed."""
        from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind

        self._seed(instance, token="dev-1")
        self._seed2 = None
        instance.device_management.create_device(
            token="dev-2", device_type="sensor")
        instance.device_management.create_device_assignment(device="dev-2")
        for tok, ts in (("dev-1", 1000), ("dev-2", 1000)):
            instance.dispatcher.ingest(DecodedRequest(
                kind=RequestKind.MEASUREMENT, device_token=tok,
                ts_s=ts, mtype="temp", value=1.0))
        instance.dispatcher.flush()
        # sweep marks both missing (now far past missing_after)
        instance.device_state.apply_presence_sweep(
            now_s=10_000, missing_after_s=1800)
        ids = instance.device_state.missing_device_ids()
        assert len(ids) == 2
        # a fresh event for dev-1 clears it; dev-2 stays missing through
        # the packed commit
        instance.dispatcher.ingest(DecodedRequest(
            kind=RequestKind.MEASUREMENT, device_token="dev-1",
            ts_s=10_100, mtype="temp", value=2.0))
        instance.dispatcher.flush()
        assert not instance.device_state.get_device_state(
            "dev-1")["presence_missing"]
        assert instance.device_state.get_device_state(
            "dev-2")["presence_missing"]


@pytest.mark.parametrize("n_shards", [1, 4], ids=["one-chip", "mesh4"])
def test_dispatcher_serves_packed_plans_only(tmp_path, n_shards):
    """A plan has one form and the dispatcher one served step: a default
    ``Instance`` emits packed plans only, every dispatched plan enters
    ``_step_packed``, the stage timers are the packed path's and no
    other, every counted host sync is the view's own timed fetch, and
    every row sent is accepted."""
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    width, n_dev, rounds = 64, 32, 3
    cfg = Config({
        "instance": {"id": "packed-only-test",
                     "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": width, "registry_capacity": 1024,
                     "mtype_slots": 4, "deadline_ms": 5.0,
                     "n_shards": n_shards},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    try:
        disp = inst.dispatcher
        stepped = []
        step_packed = disp._step_packed

        def counting_step(plan, trace):
            stepped.append(plan)
            return step_packed(plan, trace)

        disp._step_packed = counting_step
        dm = inst.device_management
        dm.create_device_type(token="sensor", name="S")
        for i in range(n_dev):
            dm.create_device(token=f"d-{i}", device_type="sensor")
            dm.create_device_assignment(device=f"d-{i}")
        handles = np.asarray(inst.identity.device.lookup_many(
            [f"d-{i}" for i in range(n_dev)]), np.int32)
        for r in range(rounds):
            disp.ingest_arrays(
                device_id=handles[np.arange(width) % n_dev],
                event_type=np.zeros(width, np.int32),
                ts_s=np.full(width, 1_753_800_000 + r, np.int32),
                mtype_id=np.zeros(width, np.int32),
                value=np.full(width, 1.0, np.float32))
        disp.flush()
        assert len(stepped) >= rounds
        assert sum(p.n_events for p in stepped) == rounds * width
        for plan in stepped:
            assert plan.packed_i.shape == (12, width)
            assert plan.packed_f.shape == (4, width)
            assert plan._batch is None   # nothing served reads plan.batch
        snap = inst.metrics.snapshot()
        stages = {"decode", "batch", "dispatch", "egress", "ring_wait",
                  "ring_dispatch", "dispatch_wait", "inflight_wait",
                  "meter"}
        if n_shards > 1:
            stages.add("place")
        assert {n for n in snap["timers"]
                if n.startswith("pipeline.stage_")} == {
                    f"pipeline.stage_{s}_s" for s in stages}
        syncs = snap["counters"]["pipeline.host_syncs"]
        assert syncs >= 1
        assert snap["timers"]["pipeline.device_wait_s"]["count"] == syncs
        assert disp.metrics_snapshot()["accepted"] == rounds * width
    finally:
        inst.stop()
        inst.terminate()

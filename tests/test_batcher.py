"""Batcher: routing, deadline, carry-over, and end-to-end-with-pipeline tests."""

import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.ingest.batcher import Batcher
from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind
from sitewhere_tpu.parallel.mesh import shard_for_device

CAP = 64
N_SHARDS = 4
WIDTH = 16  # 4 rows per shard


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def make_batcher(deadline_ms=5.0, clock=None, devices=None):
    devices = devices if devices is not None else {}
    mtypes = {}
    alerts = {}

    def resolve_device(token):
        return devices.get(token, NULL_ID)

    def resolve(table):
        def fn(name):
            return table.setdefault(name, len(table))
        return fn

    return Batcher(
        width=WIDTH, n_shards=N_SHARDS, registry_capacity=CAP,
        resolve_device=resolve_device, resolve_mtype=resolve(mtypes),
        resolve_alert=resolve(alerts), deadline_ms=deadline_ms,
        clock=clock or FakeClock(),
    )


def meas(token, ts=1000, value=1.0, mtype="temp"):
    return DecodedRequest(kind=RequestKind.MEASUREMENT, device_token=token,
                          ts_s=ts, mtype=mtype, value=value)


def test_routing_respects_shard_ownership():
    devices = {f"d{i}": i for i in range(CAP)}
    b = make_batcher(devices=devices)
    b.add(meas("d0"), tenant_id=0, payload_ref=100)    # shard 0
    b.add(meas("d17"), tenant_id=0, payload_ref=101)   # 17 // 16 = shard 1
    b.add(meas("d63"), tenant_id=0, payload_ref=102)   # shard 3
    plan = b.flush()
    batch = plan.batch
    seg = WIDTH // N_SHARDS
    ids = np.asarray(batch.device_id)
    valid = np.asarray(batch.valid)
    for pos, did in [(0 * seg, 0), (1 * seg, 17), (3 * seg, 63)]:
        assert valid[pos] and ids[pos] == did
        assert shard_for_device(did, CAP, N_SHARDS) == pos // seg
    assert plan.n_events == 3
    assert np.asarray(batch.payload_ref)[0] == 100


def test_unknown_device_round_robins_with_null_id():
    b = make_batcher()
    for i in range(3):
        b.add(meas(f"ghost-{i}"), tenant_id=0, payload_ref=i)
    plan = b.flush()
    ids = np.asarray(plan.batch.device_id)
    valid = np.asarray(plan.batch.valid)
    assert valid.sum() == 3
    assert (ids[valid] == NULL_ID).all()  # dead-letters on device


def test_emit_when_segment_fills():
    devices = {f"d{i}": i for i in range(CAP)}
    b = make_batcher(devices=devices)
    seg = WIDTH // N_SHARDS
    plan = None
    for i in range(seg):  # all to shard 0 (devices 0..3 are in block 0)
        plan = b.add(meas(f"d{i}"), tenant_id=0, payload_ref=i)
    assert plan is not None  # filled shard 0 segment
    assert plan.n_events == seg


def test_deadline_emission():
    clock = FakeClock()
    b = make_batcher(deadline_ms=5.0, clock=clock)
    b.add(meas("x"), tenant_id=0, payload_ref=0)
    assert b.poll() is None          # deadline not reached
    clock.t = 0.004
    assert b.poll() is None
    clock.t = 0.0051
    plan = b.poll()
    assert plan is not None
    assert plan.n_events == 1
    assert plan.max_wait_s >= 0.005
    assert b.poll() is None          # drained


def test_overflow_carries_over():
    devices = {f"d{i}": i for i in range(CAP)}
    clock = FakeClock()
    b = make_batcher(devices=devices, clock=clock)
    seg = WIDTH // N_SHARDS
    # 6 events for shard 0 (only 4 fit per batch).
    plans = [p for i in range(6)
             if (p := b.add(meas(f"d{i % 4}", ts=1000 + i), tenant_id=0,
                            payload_ref=i)) is not None]
    assert len(plans) == 1
    assert plans[0].n_events == seg
    assert b.pending == 2
    # Carried rows keep their arrival time: deadline fires without new adds.
    clock.t = 1.0
    plan2 = b.poll()
    assert plan2 is not None and plan2.n_events == 2


def test_host_plane_request_rejected():
    b = make_batcher()
    reg = DecodedRequest(kind=RequestKind.REGISTRATION, device_token="d", ts_s=1)
    import pytest
    with pytest.raises(ValueError):
        b.add(reg, tenant_id=0, payload_ref=0)


def test_batcher_feeds_pipeline_end_to_end():
    """Decoded JSON -> batcher -> jitted pipeline step (the §7 build-plan
    'minimum end-to-end slice')."""
    import jax
    import json
    from sitewhere_tpu.ingest.decoders import JsonDecoder
    from sitewhere_tpu.pipeline import pipeline_step
    from sitewhere_tpu.schema import DeviceState, RuleTable, ZoneTable
    from helpers import make_registry

    devices = {f"d{i}": i for i in range(8)}
    b = make_batcher(devices=devices)
    payload = json.dumps({"deviceToken": "d1", "type": "Measurement",
                          "request": {"name": "temp", "value": 70.5,
                                      "eventDate": 1000}}).encode()
    (req,) = JsonDecoder()(payload)
    b.add(req, tenant_id=0, payload_ref=0)
    plan = b.flush()

    reg = make_registry(capacity=CAP, n_devices=8)
    state, out = jax.jit(pipeline_step)(
        reg, DeviceState.empty(CAP), RuleTable.empty(4), ZoneTable.empty(4),
        plan.batch,
    )
    assert int(out.metrics.accepted) == 1
    assert float(state.last_values[1, 0]) == 70.5


# -- vectorized columnar intake (add_arrays / add_requests) -----------------

def test_add_arrays_routes_by_shard_and_fills_defaults():
    devices = {f"d{i}": i for i in range(CAP)}
    b = make_batcher(devices=devices)
    plans = b.add_arrays(
        device_id=np.array([0, 17, 63], np.int32),
        value=np.array([1.0, 2.0, 3.0], np.float32),
    )
    assert plans == []
    plan = b.flush()
    batch = plan.batch
    seg = WIDTH // N_SHARDS
    ids = np.asarray(batch.device_id)
    vals = np.asarray(batch.value)
    assert ids[0 * seg] == 0 and vals[0 * seg] == 1.0
    assert ids[1 * seg] == 17 and vals[1 * seg] == 2.0
    assert ids[3 * seg] == 63 and vals[3 * seg] == 3.0
    # omitted columns take fills
    assert np.asarray(batch.payload_ref)[0 * seg] == NULL_ID
    assert bool(np.asarray(batch.update_state)[0 * seg])


def test_add_arrays_emits_multiple_plans_for_large_input():
    devices = {f"d{i}": i for i in range(CAP)}
    b = make_batcher(devices=devices)
    # 3 segments worth of rows on shard 0 -> at least 2 full plans queued
    n = 3 * (WIDTH // N_SHARDS)
    plans = b.add_arrays(device_id=np.zeros(n, np.int32))
    assert len(plans) >= 2
    total = sum(p.n_events for p in plans)
    rest = b.flush()
    if rest is not None:
        total += rest.n_events
    assert total == n


def test_add_arrays_unknown_devices_round_robin_null():
    b = make_batcher()
    plans = b.add_arrays(
        device_id=np.array([999, -5, 123456], np.int32))
    plan = plans[0] if plans else b.flush()
    ids = np.asarray(plan.batch.device_id)[np.asarray(plan.batch.valid)]
    assert (ids == NULL_ID).all()
    assert plan.n_events == 3


def test_add_arrays_rejects_bad_columns():
    b = make_batcher()
    import pytest

    with pytest.raises(ValueError):
        b.add_arrays(device_id=np.array([0]), bogus=np.array([1]))
    with pytest.raises(ValueError):
        b.add_arrays(device_id=np.array([0, 1]), value=np.array([1.0]))


def test_add_requests_matches_scalar_path():
    devices = {f"d{i}": i for i in range(CAP)}
    b1 = make_batcher(devices=devices)
    b2 = make_batcher(devices=devices)
    reqs = [meas(f"d{i}", ts=1000 + i, value=float(i)) for i in range(6)]
    for r in reqs:
        b1.add(r, tenant_id=2, payload_ref=7)
    b2.add_requests(reqs, tenant_ids=[2] * 6, payload_refs=[7] * 6)
    p1, p2 = b1.flush(), b2.flush()
    for f in ("device_id", "tenant_id", "event_type", "ts_s", "value",
              "mtype_id", "payload_ref", "valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(p1.batch, f)), np.asarray(getattr(p2.batch, f)),
            err_msg=f)


def test_mixed_scalar_and_array_intake_preserves_fifo_per_shard():
    devices = {f"d{i}": i for i in range(CAP)}
    b = make_batcher(devices=devices)
    b.add(meas("d0", value=1.0), tenant_id=0, payload_ref=NULL_ID)
    b.add_arrays(device_id=np.array([1], np.int32),
                 value=np.array([2.0], np.float32))
    b.add(meas("d2", value=3.0), tenant_id=0, payload_ref=NULL_ID)
    plan = b.flush()
    vals = np.asarray(plan.batch.value)[:3]
    np.testing.assert_array_equal(vals, [1.0, 2.0, 3.0])


def test_staging_chunk_carryover_does_not_resurrect_rows():
    devices = {f"d{i}": i for i in range(CAP)}
    b = make_batcher(devices=devices)
    seg = WIDTH // N_SHARDS
    # fill shard 0's segment + 1 carry-over row via the scalar path
    plans = []
    for i in range(seg + 1):
        p = b.add(meas("d0", value=float(i)), tenant_id=0, payload_ref=NULL_ID)
        if p is not None:
            plans.append(p)
    assert len(plans) == 1 and plans[0].n_events == seg
    rest = b.flush()
    assert rest.n_events == 1
    assert np.asarray(rest.batch.value)[0] == float(seg)
    assert b.pending == 0 and b.flush() is None


def test_add_arrays_reuses_fill_templates_without_allocation():
    """Satellite fix: omitted columns must not allocate a full column per
    call — they are 0-stride broadcast views of the shared templates."""
    b = Batcher(
        width=8, n_shards=1, registry_capacity=CAP,
        resolve_device=lambda t: NULL_ID, resolve_mtype=lambda n: 0,
        resolve_alert=lambda n: 0, deadline_ms=5.0, clock=FakeClock())
    b.add_arrays(_copy=False, device_id=np.array([0, 1, 2], np.int32))
    chunk = b._pending[0][0]
    fill = chunk.cols["value"]
    assert fill.strides == (0,)          # broadcast view, not np.full
    assert not fill.flags.writeable
    # emission still materializes correct fill values into the batch
    plan = b.flush()
    assert plan.host_cols["value"][:3].tolist() == [0.0, 0.0, 0.0]
    assert plan.host_cols["payload_ref"][:3].tolist() == [NULL_ID] * 3


def test_add_arrays_no_copy_fast_path_for_typed_inputs():
    """_copy=False + already-typed arrays: queued columns ARE the caller
    arrays (zero copies on the internal hot path)."""
    b = Batcher(
        width=8, n_shards=1, registry_capacity=CAP,
        resolve_device=lambda t: NULL_ID, resolve_mtype=lambda n: 0,
        resolve_alert=lambda n: 0, deadline_ms=5.0, clock=FakeClock())
    val = np.array([1.0, 2.0, 3.0], np.float32)
    b.add_arrays(_copy=False, device_id=np.array([0, 1, 2], np.int32),
                 value=val)
    assert b._pending[0][0].cols["value"] is val


# -- adaptive batch-width controller ----------------------------------------

def make_adaptive(deadline_ms=5.0, **kw):
    from sitewhere_tpu.ingest.batcher import AdaptiveBatchController

    return AdaptiveBatchController(deadline_ms=deadline_ms, **kw)


def test_adaptive_shrinks_under_idle_and_grows_under_backlog():
    """Acceptance: deterministic (fake-clock) shrink-under-idle and
    grow-under-backlog, driven through the batcher itself."""
    clock = FakeClock()
    ctl = make_adaptive(deadline_ms=5.0, min_ms=1.25, max_ms=40.0)
    devices = {f"d{i}": i for i in range(CAP)}
    b = make_batcher(devices=devices, clock=clock)
    b.controller = ctl
    base = 0.005
    assert b.deadline_s == base

    # idle: single low-fill rows emitted on deadline → window shrinks
    clock.t = 0.0
    b.add(meas("d0"), tenant_id=0, payload_ref=0)
    clock.t = base + 0.001
    assert b.poll() is not None
    assert ctl.shrinks == 1
    assert b.deadline_s == pytest.approx(base * 0.75)

    # keep idling: monotonically down to the floor, never below
    for i in range(20):
        b.add(meas("d0"), tenant_id=0, payload_ref=0)
        clock.t += 1.0
        assert b.poll() is not None
    assert b.deadline_s == pytest.approx(0.00125)

    # backlog: segment-fill emissions → window grows toward the cap
    seg = WIDTH // N_SHARDS
    grows_before = ctl.grows
    for _ in range(40):
        plans = b.add_arrays(device_id=np.zeros(seg, np.int32))
        assert plans  # shard 0's segment filled → pressure signal
    assert ctl.grows > grows_before
    assert b.deadline_s == pytest.approx(0.040)

    # decision counts: 5 shrinks reach the floor (0.75^5), 9 grows reach
    # the cap (1.5^9) — saturated emits are not counted as decisions
    assert ctl.shrinks == 5 and ctl.grows == 9


def test_deadline_setter_writes_through_to_controller():
    ctl = make_adaptive(deadline_ms=5.0, min_ms=1.25, max_ms=40.0)
    b = make_batcher()
    b.controller = ctl
    # an explicit set re-anchors the adaptive window (clamped)
    b.deadline_s = 0.010
    assert b.deadline_s == pytest.approx(0.010)
    b.deadline_s = 0.0001  # below the floor: clamps, never silently lost
    assert b.deadline_s == pytest.approx(0.00125)


def test_adaptive_flush_and_moderate_fill_do_not_adapt():
    clock = FakeClock()
    ctl = make_adaptive(deadline_ms=5.0)
    devices = {f"d{i}": i for i in range(CAP)}
    b = make_batcher(devices=devices, clock=clock)
    b.controller = ctl
    # flush emits never adapt (shutdown artifacts)
    b.add(meas("d0"), tenant_id=0, payload_ref=0)
    assert b.flush() is not None
    assert ctl.grows == ctl.shrinks == 0
    # deadline emit at moderate fill (above low_fill): window holds
    for i in range(8):  # 8 of 16 = 50% fill, spread across shards
        b.add(meas(f"d{i * 8 % CAP}"), tenant_id=0, payload_ref=0)
    clock.t += 1.0
    assert b.poll() is not None
    assert ctl.shrinks == 0 and ctl.grows == 0
    assert b.deadline_s == 0.005


def test_adaptive_exports_decisions_to_metrics():
    from sitewhere_tpu.runtime.metrics import MetricsRegistry

    m = MetricsRegistry()
    ctl = make_adaptive(deadline_ms=5.0, metrics=m)
    ctl.on_emit(1, 16, 0, "deadline")     # idle → shrink
    ctl.on_emit(16, 16, 32, "fill")       # backlog → grow
    snap = m.snapshot()
    assert snap["counters"]["ingest.adaptive_shrink"] == 1
    assert snap["counters"]["ingest.adaptive_grow"] == 1
    assert snap["gauges"]["ingest.adaptive_window_s"] == ctl.window_s


def test_adaptive_ignores_idle_emits():
    """An idle emission waited for no window, so it says nothing about
    coalescing: the emit a deadline plan of the same fill would shrink
    on leaves the window where it was."""
    ctl = make_adaptive(deadline_ms=5.0)
    ctl.on_emit(1, 16, 0, "idle")
    ctl.on_emit(16, 16, 32, "idle")
    assert ctl.window_s == 0.005 and ctl.shrinks == ctl.grows == 0
    ctl.on_emit(1, 16, 0, "deadline")
    assert ctl.shrinks == 1


def test_emit_idle_emits_what_is_pending_and_counts_its_rows():
    from sitewhere_tpu.runtime.metrics import MetricsRegistry

    m = MetricsRegistry()
    ctl = make_adaptive(deadline_ms=5.0)
    b = ladder_batcher(metrics=m, controller=ctl)
    assert b.emit_idle() is None          # nothing pending, no plan
    b.add_arrays(device_id=np.arange(1000, dtype=np.int32))
    plan = b.emit_idle()
    assert (plan.reason, plan.n_events, plan.width) == ("idle", 1000, 2048)
    assert b.pending == 0 and b.emit_idle() is None
    counters = m.snapshot()["counters"]
    assert counters["ingest.rows_emitted_idle"] == 1000
    assert counters["ingest.rows_emitted"] == 1000
    assert ctl.window_s == 0.005


def test_add_arrays_single_shard_copies_caller_arrays():
    """ingest_arrays advertises vectorized/ring-buffer feeders; a caller
    refilling its buffers while rows sit queued must not corrupt queued
    events (round-2 advisor finding)."""
    b = Batcher(
        width=8, n_shards=1, registry_capacity=CAP,
        resolve_device=lambda t: NULL_ID, resolve_mtype=lambda n: 0,
        resolve_alert=lambda n: 0, deadline_ms=5.0, clock=FakeClock())
    dev = np.array([0, 1, 2], np.int32)
    val = np.array([1.0, 2.0, 3.0], np.float32)
    assert b.add_arrays(device_id=dev, value=val) == []
    dev[:] = 99  # caller reuses its buffers
    val[:] = -1.0
    plan = b.flush()
    got_dev = plan.host_cols["device_id"][:3].tolist()
    got_val = plan.host_cols["value"][:3].tolist()
    assert got_dev == [0, 1, 2]
    assert got_val == [1.0, 2.0, 3.0]


def _emit_partial(b, reason):
    """What is pending, emitted the way ``reason`` names: the loop's
    deadline poll, a drain's flush, or the wire intake's idle emission."""
    return {"deadline": b.poll, "flush": b.flush,
            "idle": b.emit_idle}[reason]()


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("reason", ["fill", "deadline", "flush", "idle"])
def test_plan_views_agree(reason, n_shards):
    """A plan has one form: whatever made the batcher emit it, it
    carries the packed buffers the dispatcher stages, and they unpack to
    ``plan.batch`` (the plan read from ``host_cols``) column by column."""
    import dataclasses

    from sitewhere_tpu.pipeline.packed import unpack_batch

    clock = FakeClock()
    b = Batcher(width=WIDTH, n_shards=n_shards, registry_capacity=CAP,
                resolve_device=lambda t: NULL_ID, resolve_mtype=lambda n: 0,
                resolve_alert=lambda n: 0, deadline_ms=5.0, clock=clock)
    # "fill" fills every shard's segment at once; the others leave a
    # partial in which no segment is full
    n, stride = (WIDTH, CAP // WIDTH) if reason == "fill" else (5, 13)
    ids = np.arange(n, dtype=np.int32) * stride
    plans = b.add_arrays(
        device_id=ids, ts_s=np.arange(n, dtype=np.int32) + 1000,
        value=np.linspace(0.0, 1.0, n).astype(np.float32),
        lat=np.full(n, 3.5, np.float32),
        update_state=(np.arange(n) % 2 == 0))
    if reason != "fill":
        clock.t = 1.0
        plans = [_emit_partial(b, reason)]
    (plan,) = plans
    assert plan.reason == reason and plan.n_events == n
    assert plan.packed_i is not None and plan.packed_f is not None
    got, want = unpack_batch(plan.packed_i, plan.packed_f), plan.batch
    for f in dataclasses.fields(want):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f.name)),
            np.asarray(getattr(want, f.name)), err_msg=f.name)
    assert int(np.asarray(want.valid).sum()) == n


# -- the width ladder (PR 33): a partial plan is assembled at the width it
# -- needs --------------------------------------------------------------

LADDER_W = 8192  # the smallest width with four distinct rungs


def ladder_batcher(width=LADDER_W, n_shards=1, clock=None, **kw):
    return Batcher(width=width, n_shards=n_shards, registry_capacity=1 << 16,
                   resolve_device=lambda t: NULL_ID,
                   resolve_mtype=lambda n: 0, resolve_alert=lambda n: 0,
                   deadline_ms=5.0, clock=clock or FakeClock(), **kw)


@pytest.mark.parametrize("width, rungs", [
    (65536, (1024, 4096, 16384, 65536)),
    (16384, (256, 1024, 4096, 16384)),
    (8192, (128, 512, 2048, 8192)),
    (1024, (128, 256, 1024)),     # no rung under one lane tile
    (256, (128, 256)),
    (128, (128,)),
    (64, (64,)),
    (8, (8,)),
])
def test_plan_rungs_are_a_fixed_function_of_the_width(width, rungs):
    from sitewhere_tpu.ingest.batcher import plan_rungs

    assert plan_rungs(width) == rungs
    assert len(rungs) <= 4 and rungs[-1] == width
    assert ladder_batcher(width=width).rungs == rungs


def _rung_cases():
    from sitewhere_tpu.ingest.batcher import plan_rungs

    rungs = plan_rungs(LADDER_W)
    for i, rung in enumerate(rungs):
        yield rung - 1, rung                 # one under
        yield rung, rung                     # at
        if i + 1 < len(rungs):
            yield rung + 1, rungs[i + 1]     # one over


@pytest.mark.parametrize("reason", ["deadline", "flush", "idle"])
@pytest.mark.parametrize("n, rung", sorted(set(_rung_cases())))
def test_partial_emission_takes_the_smallest_rung_that_holds_it(
        reason, n, rung):
    from sitewhere_tpu.pipeline.packed import BATCH_F, BATCH_I

    clock = FakeClock()
    b = ladder_batcher(clock=clock)
    ids = np.arange(n, dtype=np.int32)
    emitted = b.add_arrays(device_id=ids, value=ids.astype(np.float32),
                           update_state=ids % 2 == 0)
    if n == LADDER_W:
        # a full width goes out as it fills, whatever would have come
        (plan,) = emitted
        assert plan.reason == "fill"
    else:
        assert emitted == []
        clock.t = 1.0
        plan = _emit_partial(b, reason)
        assert plan.reason == reason
    assert plan.n_events == n
    assert plan.width == rung and plan.full_width == LADDER_W
    assert plan.fill == n / LADDER_W      # of the configured width
    assert plan.packed_i.shape == (len(BATCH_I), rung)
    assert plan.packed_f.shape == (len(BATCH_F), rung)
    assert set(plan.host_cols) == set(BATCH_I) | set(BATCH_F)
    for f, col in plan.host_cols.items():
        assert col.shape == (rung,), f
    valid, upd = plan.host_cols["valid"], plan.host_cols["update_state"]
    assert valid.dtype == np.bool_ and upd.dtype == np.bool_
    assert valid[:n].all() and not valid[n:].any()
    np.testing.assert_array_equal(upd[:n], ids % 2 == 0)
    np.testing.assert_array_equal(plan.packed_i[0], valid.astype(np.int32))
    np.testing.assert_array_equal(plan.host_cols["device_id"][:n], ids)
    assert (plan.host_cols["device_id"][n:] == NULL_ID).all()
    assert b.pending == 0


def test_fill_emission_is_full_width_and_carries_the_rest_narrow():
    b = ladder_batcher()
    n = LADDER_W + 300
    (plan,) = b.add_arrays(device_id=np.arange(n, dtype=np.int32))
    assert plan.reason == "fill"
    assert plan.width == plan.full_width == plan.n_events == LADDER_W
    rest = b.flush()
    assert (rest.n_events, rest.width) == (300, 512)
    np.testing.assert_array_equal(
        rest.host_cols["device_id"][:300], np.arange(LADDER_W, n))


@pytest.mark.parametrize("n", [LADDER_W, 700])
def test_adopted_lane_is_unchanged(n):
    """A full-width reservation is the batch, full or partial: the
    zero-copy lane keeps the configured width and copies nothing."""
    b = ladder_batcher()
    res = b.reserve(LADDER_W)
    res.device_id[:n] = np.arange(n)
    res.mtype_id[:n] = 0
    res.ts_s[:n] = 1000
    res.ts_ns[:n] = 0
    res.update_state[:n] = 1
    res.value[:n] = 1.0
    res.set_const(tenant_id=0, payload_ref=5)
    res.n = n
    plans = res.commit()
    plan = plans[0] if n == LADDER_W else b.flush()
    assert plan.packed_i is res.ibuf and plan.packed_f is res.fbuf
    assert plan.width == plan.full_width == LADDER_W
    assert plan.n_events == n and b.copied_bytes == 0


@pytest.mark.parametrize("n", [100, 128, 600])
def test_emit_tail_reads_the_configured_width(n):
    """A rung-full deadline plan is not a full one to the adaptive
    deadline or to ``ingest.batch_fill``."""
    from sitewhere_tpu.runtime.metrics import MetricsRegistry

    seen = []

    class Ctl:
        deadline_s = 0.005

        def on_emit(self, n_events, width, pending, reason):
            seen.append((n_events, width, pending, reason))

    clock, m = FakeClock(), MetricsRegistry()
    b = ladder_batcher(clock=clock, metrics=m, controller=Ctl())
    b.add_arrays(device_id=np.arange(n, dtype=np.int32))
    clock.t = 1.0
    plan = b.poll()
    assert plan.width < LADDER_W
    assert seen == [(n, LADDER_W, 0, "deadline")]
    assert m.snapshot()["gauges"]["ingest.batch_fill"] == n / LADDER_W


@pytest.mark.parametrize("reason", ["deadline", "flush", "idle"])
@pytest.mark.parametrize("n", [1, 100, 129, 2049])
def test_sharded_batcher_emits_at_one_width(reason, n):
    clock = FakeClock()
    b = ladder_batcher(n_shards=4, clock=clock)
    assert b.rungs == (LADDER_W,)
    b.add_arrays(device_id=(np.arange(n, dtype=np.int32) * 31) % (1 << 16))
    clock.t = 1.0
    plan = _emit_partial(b, reason)
    assert plan.n_events == n
    assert plan.width == plan.full_width == LADDER_W
    assert plan.packed_i.shape[1] == LADDER_W
    assert plan.host_cols["valid"].shape == (LADDER_W,)

"""Sharded pipeline equivalence + routing tests on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.parallel.mesh import shard_for_device
from sitewhere_tpu.pipeline import pipeline_step
from sitewhere_tpu.pipeline.sharded import (
    build_sharded_step,
    place_batch,
    place_inputs,
)
from sitewhere_tpu.schema import DeviceState, EventType, RuleTable, ZoneTable
from sitewhere_tpu.ids import NULL_ID

from helpers import (
    location,
    make_batch,
    make_registry,
    measurement,
    square_zone,
    threshold_rule,
)

CAP = 64  # 8 rows per shard on the 8-device mesh
N_SHARDS = 8
WIDTH = 32  # 4 rows per shard


def route_rows(rows):
    """Place each event row in its owning shard's segment of the batch.

    This is what the host batcher does (the keyed-Kafka-partitioner analog):
    shard k owns batch positions [k*W/N, (k+1)*W/N).
    """
    per_shard = WIDTH // N_SHARDS
    segments = [[] for _ in range(N_SHARDS)]
    for row in rows:
        did = row["device_id"]
        if 0 <= did < CAP:
            shard = shard_for_device(did, CAP, N_SHARDS)
        else:
            shard = 0  # unknown device: batcher picks any shard (dead-letters)
        segments[shard].append(row)
    placed = []
    for seg in segments:
        assert len(seg) <= per_shard, "test routed too many rows to one shard"
        placed.extend(seg + [{"valid": False}] * (per_shard - len(seg)))
    return make_batch(placed)


def setup(mesh):
    reg = make_registry(capacity=CAP, n_devices=CAP)  # all slots active
    state = DeviceState.empty(CAP)
    rules = threshold_rule(RuleTable.empty(4), 0, mtype=0, op=0, threshold=50.0,
                           alert_code=200)
    zones = square_zone(ZoneTable.empty(4), 0, 0, 0, 10, 10, alert_code=100)
    return place_inputs(mesh, reg, state, rules, zones)


def test_sharded_matches_single_chip(mesh8):
    rows = [
        measurement(device=3, mtype=0, value=75.0, ts=1000),   # shard 0, fires
        measurement(device=9, mtype=0, value=25.0, ts=1000),   # shard 1
        location(device=17, lon=5.0, lat=5.0, ts=1000),        # shard 2, in zone
        location(device=25, lon=50.0, lat=5.0, ts=1000),       # shard 3
        measurement(device=63, mtype=1, value=1.0, ts=1000),   # shard 7
        measurement(device=200, ts=1000),                      # unregistered
    ]
    batch = route_rows(rows)

    # Reference: single-chip step on the same (already routed) batch.
    reg = make_registry(capacity=CAP, n_devices=CAP)
    rules = threshold_rule(RuleTable.empty(4), 0, mtype=0, op=0, threshold=50.0,
                           alert_code=200)
    zones = square_zone(ZoneTable.empty(4), 0, 0, 0, 10, 10, alert_code=100)
    ref_state, ref_out = jax.jit(pipeline_step)(
        reg, DeviceState.empty(CAP), rules, zones, batch
    )

    s_reg, s_state, s_rules, s_zones = setup(mesh8)
    step = build_sharded_step(mesh8)
    new_state, out = step(s_reg, s_state, s_rules, s_zones,
                          place_batch(mesh8, batch))

    # Row-level outputs identical.
    np.testing.assert_array_equal(np.asarray(out.accepted), np.asarray(ref_out.accepted))
    np.testing.assert_array_equal(np.asarray(out.unregistered),
                                  np.asarray(ref_out.unregistered))
    np.testing.assert_array_equal(np.asarray(out.rule_id), np.asarray(ref_out.rule_id))
    np.testing.assert_array_equal(np.asarray(out.zone_id), np.asarray(ref_out.zone_id))
    np.testing.assert_array_equal(np.asarray(out.area_id), np.asarray(ref_out.area_id))
    # Derived alerts carry global device ids.
    np.testing.assert_array_equal(np.asarray(out.derived_alerts.device_id),
                                  np.asarray(ref_out.derived_alerts.device_id))
    # State identical.
    for f in ("last_event_ts_s", "last_values", "last_lat", "last_event_type"):
        np.testing.assert_array_equal(np.asarray(getattr(new_state, f)),
                                      np.asarray(getattr(ref_state, f)))
    # Metrics identical (psum over shards == global sums).
    assert int(out.metrics.processed) == int(ref_out.metrics.processed) == 6
    assert int(out.metrics.accepted) == int(ref_out.metrics.accepted) == 5
    assert int(out.metrics.threshold_alerts) == 1
    assert int(out.metrics.zone_alerts) == 1


def test_misrouted_event_dead_letters(mesh8):
    # Device 63 (shard 7) placed in shard 0's segment: local gather can't
    # validate it -> unregistered dead-letter for host re-route.
    per_shard = WIDTH // N_SHARDS
    rows = [measurement(device=63, ts=1000)] + [{"valid": False}] * (WIDTH - 1)
    batch = make_batch(rows)
    s_reg, s_state, s_rules, s_zones = setup(mesh8)
    step = build_sharded_step(mesh8)
    _, out = step(s_reg, s_state, s_rules, s_zones, place_batch(mesh8, batch))
    assert bool(out.unregistered[0])
    assert not bool(out.accepted[0])
    assert int(out.metrics.unregistered) == 1


def test_sharded_state_stays_sharded(mesh8):
    """The state must come back with the same sharding it went in with —
    steady-state steps must not trigger resharding transfers."""
    batch = route_rows([measurement(device=3, ts=1000)])
    s_reg, s_state, s_rules, s_zones = setup(mesh8)
    step = build_sharded_step(mesh8)
    in_sharding = s_state.last_event_ts_s.sharding
    new_state, _ = step(s_reg, s_state, s_rules, s_zones, place_batch(mesh8, batch))
    assert new_state.last_event_ts_s.sharding == in_sharding
    # And it can be fed straight back in.
    new_state2, _ = step(s_reg, new_state, s_rules, s_zones,
                         place_batch(mesh8, batch))
    assert int(new_state2.last_event_ts_s[3]) == 1000


def test_sharded_packed_matches_single_chip(mesh8):
    """The packed mesh form (deployment config): same outputs and state
    as the single-chip unpacked step, through the [C, B]-sharded wire
    interface."""
    from sitewhere_tpu.pipeline.packed import (
        PackedView,
        pack_batch_host,
        pack_state,
        pack_tables,
        unpack_state,
    )
    from sitewhere_tpu.pipeline.sharded import (
        build_sharded_packed_step,
        place_packed_batch,
        place_packed_state,
        place_packed_tables,
    )
    from sitewhere_tpu.schema import as_numpy

    rows = [
        measurement(device=3, mtype=0, value=75.0, ts=1000),
        measurement(device=9, mtype=0, value=25.0, ts=1000),
        location(device=17, lon=5.0, lat=5.0, ts=1000),
        location(device=25, lon=50.0, lat=5.0, ts=1000),
        measurement(device=63, mtype=1, value=1.0, ts=1000),
        measurement(device=200, ts=1000),
    ]
    batch = route_rows(rows)

    reg = make_registry(capacity=CAP, n_devices=CAP)
    rules = threshold_rule(RuleTable.empty(4), 0, mtype=0, op=0,
                           threshold=50.0, alert_code=200)
    zones = square_zone(ZoneTable.empty(4), 0, 0, 0, 10, 10, alert_code=100)
    ref_state, ref_out = jax.jit(pipeline_step)(
        reg, DeviceState.empty(CAP), rules, zones, batch)
    ref = as_numpy(ref_out)

    # packed + placed inputs
    tables = place_packed_tables(mesh8, pack_tables(reg, rules, zones))
    ps = place_packed_state(mesh8, pack_state(DeviceState.empty(CAP)))
    cols = {f: np.asarray(getattr(as_numpy(batch), f))
            for f in batch.__dataclass_fields__}
    bi, bf = pack_batch_host(cols, WIDTH)
    bi, bf = place_packed_batch(mesh8, bi, bf)

    step = build_sharded_packed_step(mesh8)
    new_ps, oi, metrics, present = step(tables, ps, bi, bf)

    view = PackedView(oi, metrics, present)
    np.testing.assert_array_equal(np.asarray(ref.accepted), view.accepted)
    np.testing.assert_array_equal(np.asarray(ref.unregistered),
                                  view.unregistered)
    np.testing.assert_array_equal(np.asarray(ref.rule_id), view.rule_id)
    np.testing.assert_array_equal(np.asarray(ref.zone_id), view.zone_id)
    np.testing.assert_array_equal(np.asarray(ref.area_id), view.area_id)
    np.testing.assert_array_equal(np.asarray(ref.present_now),
                                  np.asarray(view.present_now))
    got_state = unpack_state(new_ps)
    for f in ("last_event_ts_s", "last_values", "last_lat",
              "last_event_type"):
        np.testing.assert_array_equal(np.asarray(getattr(ref_state, f)),
                                      np.asarray(getattr(got_state, f)),
                                      err_msg=f)
    m = view.metrics
    assert int(m.processed) == 6 and int(m.accepted) == 5
    assert int(m.threshold_alerts) == 1 and int(m.zone_alerts) == 1
    # steady-state: the packed carry keeps its sharding
    for new, old in zip(jax.tree.leaves(new_ps), jax.tree.leaves(ps)):
        assert new.sharding == old.sharding
        assert len(new.sharding.device_set) == 8

"""Wire-efficient packed form of the fused pipeline step.

The per-call dispatch cost of a jitted program scales with the number of
argument/result BUFFERS, not bytes: the unpacked step moves ~60 input
leaves (Registry 9 + DeviceState 16 + RuleTable 10 + ZoneTable 8 +
EventBatch 16) and ~50 output leaves per call.  This module packs the
step's interface into ELEVEN buffers total:

  inputs:  PackedTables (6: epoch-cached) + PackedState (1, donated)
           + batch ints [12, B] + batch floats [4, B]
  outputs: PackedState' (1) + out ints [10, B] + metrics [n] + present[D]
           (metrics = step scalars + per-type counts + the on-device
           occupancy telemetry block, ``TELEMETRY_SCALARS`` + the
           per-tenant attribution block, ``TENANT_METER_*``)

Column-major ``[C, B]`` layout for the batch and the tables, so every
unpacked column is a contiguous row slice (free under XLA fusion) and the
host packs each column with one memcpy.  The state carry is the other way
round, one row per device (:class:`PackedState`), because the step reads
and writes it by device.  The packed step runs the SAME
:func:`~sitewhere_tpu.pipeline.step.fused_step` as :func:`pipeline_step`,
gathering from and scattering into the packed carry directly — verified
bit-exact against the unpacked step by ``tests/test_packed.py``.

Reference framing: this is the TPU analog of the reference batching its
Kafka payloads into ONE record batch per poll instead of per-event RPCs
(``MicroserviceKafkaConsumer.java:123-128``) — amortize the per-call
envelope, keep the payload identical.
"""

from __future__ import annotations

import contextlib
import logging
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

logger = logging.getLogger("sitewhere_tpu.packed")

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.ops.scatter import merge_rows_by_id, set_rows
from sitewhere_tpu.pipeline.step import (
    NUM_EVENT_TYPES,
    PipelineOutputs,
    RowWrites,
    StateRows,
    StepMetrics,
    REGISTRY_FIELDS,
    slot_address,
    fused_step,
)
from sitewhere_tpu.schema import (
    DeviceState,
    EventBatch,
    Registry,
    RuleTable,
    ZoneTable,
)

# -- column orders (load-bearing: pack and unpack must agree) ---------------

REG_I = REGISTRY_FIELDS
RULE_I = ("active", "tenant_id", "mtype_id", "op", "alert_code",
          "alert_level", "kind", "window_idx")
ZONE_I = ("active", "tenant_id", "area_id", "nvert", "condition",
          "alert_code", "alert_level")
BATCH_I = ("valid", "device_id", "tenant_id", "event_type", "ts_s", "ts_ns",
           "mtype_id", "alert_code", "alert_level", "command_id",
           "payload_ref", "update_state")
BATCH_F = ("value", "lat", "lon", "elevation")
# Lanes of one device's row in the packed carry (PackedState.rows), each
# family's columns together; float columns are stored as their bits.  The
# measurement matrix follows from lane MEAS_LANE on, field-major:
# field j of slot m at ``MEAS_LANE + j * M + m``; the fields are ts_s,
# ts_ns, value, then the K EWMAs.
STATE_LANES = ("last_event_ts_s", "last_event_ts_ns", "last_event_type",
               "presence_missing",
               "last_location_ts_s", "last_location_ts_ns",
               "last_lat", "last_lon", "last_elevation",
               "last_alert_ts_s", "last_alert_ts_ns", "last_alert_code",
               "nonfinite_count")
STATE_FLOAT = frozenset(("last_lat", "last_lon", "last_elevation"))
MEAS_LANE = 16
OUT_I = ("flags", "device_type_id", "assignment_id", "area_id",
         "customer_id", "asset_id", "rule_id", "zone_id",
         "derived_code", "derived_level")
METRIC_SCALARS = ("processed", "accepted", "unregistered", "unassigned",
                  "threshold_alerts", "zone_alerts")
# On-device occupancy telemetry, appended after the step metrics in the
# SAME packed metrics vector — it rides the one shared D2H fetch per
# ring, so device-side visibility costs ZERO additional host syncs:
#   rows_invalid     width minus valid rows.  On a partial plan this
#                    INCLUDES batch padding (the device cannot tell a
#                    padded slot from a dropped row) — the dispatcher's
#                    device.occupancy.rows_invalid gauge subtracts the
#                    plan's real row count host-side instead
#   state_writes     rows that actually merged into DeviceState
#                    (accepted AND update_state)
#   presence_merges  devices the step's presence map marked present
#   rows_nonfinite   valid rows carrying NaN/Inf in a float column —
#                    masked out of rules/state/analytics on device; a
#                    nonzero value triggers the dispatcher's host-side
#                    quarantine scan (the rare path), so the common
#                    all-finite batch costs one fused reduction and
#                    nothing else
TELEMETRY_SCALARS = ("rows_invalid", "state_writes", "presence_merges",
                     "rows_nonfinite")

# Per-tenant attribution block, appended after TELEMETRY_SCALARS in the
# SAME packed metrics vector (PR-17 metering substrate).  Each batch's
# rows are bucketed by ``tenant_id % TENANT_METER_SLOTS`` and three
# masked counts are scatter-added per bucket in ONE segment-sum inside
# the compiled step — the block rides the shared D2H fetch per ring, so
# per-tenant device visibility costs ZERO additional host syncs and
# psums across shards like every other metrics scalar.  The host owns
# exact bucket→tenant resolution: it holds the batch's tenant column, so
# a single-tenant bucket attributes exactly and a (rare) collision
# apportions by row share (``runtime/metering.py``).
#   rows           accepted rows (admitted into the pipeline)
#   state_writes   accepted rows that merged into DeviceState
#   rows_nonfinite accepted-width rows masked for NaN/Inf floats
TENANT_METER_COUNTERS = ("rows", "state_writes", "rows_nonfinite")
TENANT_METER_SLOTS = 16
TENANT_METER_BLOCK = len(TENANT_METER_COUNTERS) * TENANT_METER_SLOTS

_LANE = {f: i for i, f in enumerate(STATE_LANES)}
PRESENCE_LANE = _LANE["presence_missing"]

# flag bits in OUT_I row 0
F_ACCEPTED = 1
F_UNREGISTERED = 2
F_UNASSIGNED = 4
F_DERIVED = 8


LANE_TILE = 128   # packed rows are padded to the chip's lane count


def registry_group(capacity: int) -> int:
    """Devices sharing one row of ``PackedTables.reg_i``: 16 (8 columns
    x 16 = one 128-lane row) wherever that leaves the table's row count
    a multiple of 64, so any mesh of up to 64 shards splits it evenly;
    fewer for the small registries of tests, 1 for odd capacities."""
    full = LANE_TILE // len(REG_I)
    return math.gcd(full, capacity // 64) if capacity % 64 == 0 else 1


def _pick_lane(got: jax.Array, onehot: jax.Array, field: int) -> jax.Array:
    """``int32[B]``: from gathered rows ``got [B, W]`` whose ``field``-th
    block of ``G = onehot.shape[1]`` lanes holds one value per group
    member, the value at each row's member (``onehot [B, G]``) — a masked
    sum over G lanes, exact on bit patterns (one term is non-zero)."""
    g = onehot.shape[1]
    lanes = got[:, field * g:(field + 1) * g]
    return jnp.where(onehot, lanes, 0).sum(axis=1, dtype=jnp.int32)


@struct.dataclass
class PackedTables:
    """Registry/rules/zones packed to six buffers (cached per epoch).

    ``reg_i`` holds ``G`` = :func:`registry_group` (16) devices a row,
    field-major within the row (field ``f`` of device ``d`` at ``[d // G,
    f * G + d % G]``): the step looks a batch up with ONE gather of B
    whole 128-lane rows (:meth:`rows_at`) and the table takes no more
    memory than its columns."""

    reg_i: jax.Array    # int32[D / G, 8 * G]
    rules_i: jax.Array  # int32[8, R]
    rules_f: jax.Array  # float32[R] — threshold
    taus: jax.Array     # float32[K] — shared EWMA time-scales
    zones_i: jax.Array  # int32[7, Z]
    zones_v: jax.Array  # float32[Z, V, 2]

    @property
    def _group(self) -> int:
        return self.reg_i.shape[1] // len(REG_I)

    @property
    def capacity(self) -> int:
        return self.reg_i.shape[0] * self._group

    def rows_at(self, ids_safe: jax.Array) -> jax.Array:
        """``int32[B, 8]`` registry columns (:data:`REG_I`) of B devices."""
        g = self._group
        got = self.reg_i[ids_safe // g]                       # [B, 8 * G]
        onehot = (ids_safe % g)[:, None] == jnp.arange(
            g, dtype=jnp.int32)[None, :]
        return jnp.stack(
            [_pick_lane(got, onehot, f) for f in range(len(REG_I))], axis=1)


def _bits(x: jax.Array) -> jax.Array:
    """A column as the carry stores it: int32, floats by their bits."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return x.astype(jnp.int32)


def _floats(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def packed_row_width(num_mtype_slots: int, num_ewma_scales: int) -> int:
    used = MEAS_LANE + num_mtype_slots * (3 + num_ewma_scales)
    return -(-used // LANE_TILE) * LANE_TILE


@struct.dataclass
class PackedState:
    """DeviceState packed to ONE buffer, the donated step carry:
    ``rows int32[D, W]``, one row per device (:data:`STATE_LANES`, then
    the measurement matrix from :data:`MEAS_LANE`; floats as their bits;
    ``W`` a multiple of the chip's 128 lanes, so a row is one contiguous
    tile line).  The step gathers the B rows a batch names, in one
    gather, and scatters the merged rows back in one unique-index
    scatter, in place when the carry is donated: it never moves the
    registry.  The layout is private to this module — readers go through
    :func:`unpack_state`, :func:`packed_presence_sweep` and
    ``DeviceStateManager``.
    """

    rows: jax.Array
    num_mtype_slots: int = struct.field(pytree_node=False, default=8)
    num_ewma_scales: int = struct.field(pytree_node=False, default=3)

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    def gather(self, batch: EventBatch) -> StateRows:
        """The batch's pre-batch state: ONE gather of B whole rows; the
        measurement fields are then picked at each row's slot by a
        one-hot sum over the M lanes of a field (batch-sized work)."""
        M, K = self.num_mtype_slots, self.num_ewma_scales
        ids_safe, slot = slot_address(batch, self.capacity, M)
        got = self.rows[ids_safe]                            # [B, W]
        onehot = slot[:, None] == jnp.arange(M, dtype=jnp.int32)[None, :]
        meas_lanes = got[:, MEAS_LANE:]

        def meas(j):
            return _pick_lane(meas_lanes, onehot, j)

        return StateRows(
            ev_s=got[:, _LANE["last_event_ts_s"]],
            ev_ns=got[:, _LANE["last_event_ts_ns"]],
            loc_s=got[:, _LANE["last_location_ts_s"]],
            loc_ns=got[:, _LANE["last_location_ts_ns"]],
            alert_s=got[:, _LANE["last_alert_ts_s"]],
            alert_ns=got[:, _LANE["last_alert_ts_ns"]],
            val_s=meas(0), val_ns=meas(1), value=_floats(meas(2)),
            ewma=_floats(jnp.stack([meas(3 + k) for k in range(K)], axis=1)),
            raw=got,
        )

    def scatter(self, batch: EventBatch, cur: StateRows, writes: RowWrites,
                ewma: jax.Array, nonfinite: Optional[jax.Array] = None,
                ) -> "PackedState":
        """Write the batch's changes back as WHOLE rows, one per device
        the batch names, in ONE scatter with unique indices.

        Several rows of a batch may change different lanes of one
        device's row (its newest location, its newest alert, a
        measurement per slot, one count per nonfinite row), so the rows
        are merged before they are written, on batch-sized arrays: each
        batch row states its change as a DIFFERENCE to the gathered row
        (``new bits - old bits`` on the lanes it writes, +1 on the
        nonfinite count, 0 elsewhere; wrapping int32, so exact), and
        :func:`~sitewhere_tpu.ops.scatter.merge_rows_by_id` sums the
        differences per device and adds them to the gathered row.  At
        most one batch row writes a given lane (:class:`RowWrites`), so
        a lane's sum is that row's difference, bit for bit.
        """
        cap, width = self.rows.shape
        M = self.num_mtype_slots
        ids = batch.device_id
        b = ids.shape[0]
        got = cur.raw
        _, slot = slot_address(batch, cap, M)
        in_range = (ids >= 0) & (ids < cap)

        def diff(write, new, lane):
            return jnp.where(write, _bits(new) - got[:, lane], 0)

        named = {
            "presence_missing": diff(writes.present, jnp.zeros_like(ids),
                                     PRESENCE_LANE),
            "nonfinite_count": (jnp.zeros_like(ids) if nonfinite is None
                                else (nonfinite & in_range).astype(jnp.int32)),
        }
        for write, fields, cols in (
                (writes.event,
                 ("last_event_ts_s", "last_event_ts_ns", "last_event_type"),
                 (batch.ts_s, batch.ts_ns, batch.event_type)),
                (writes.location,
                 ("last_location_ts_s", "last_location_ts_ns",
                  "last_lat", "last_lon", "last_elevation"),
                 (batch.ts_s, batch.ts_ns, batch.lat, batch.lon,
                  batch.elevation)),
                (writes.alert,
                 ("last_alert_ts_s", "last_alert_ts_ns", "last_alert_code"),
                 (batch.ts_s, batch.ts_ns, batch.alert_code))):
            for f, c in zip(fields, cols):
                named[f] = diff(write, c, _LANE[f])
        at_slot = writes.measurement[:, None] & (
            slot[:, None] == jnp.arange(M, dtype=jnp.int32)[None, :])
        meas = [batch.ts_s, batch.ts_ns, batch.value] + [
            ewma[:, k] for k in range(self.num_ewma_scales)]
        blocks = [jnp.stack([named[f] for f in STATE_LANES], axis=1),
                  jnp.zeros((b, MEAS_LANE - len(STATE_LANES)), jnp.int32)]
        for j, c in enumerate(meas):
            lanes = got[:, MEAS_LANE + j * M:MEAS_LANE + (j + 1) * M]
            blocks.append(jnp.where(at_slot, _bits(c)[:, None] - lanes, 0))
        used = MEAS_LANE + M * len(meas)
        blocks.append(jnp.zeros((b, width - used), jnp.int32))
        change = jnp.concatenate(blocks, axis=1)              # [B, W]

        targets, merged = merge_rows_by_id(ids, got, change, cap)
        return self.replace(rows=set_rows(self.rows, targets, merged))


def pack_tables(registry: Registry, rules: RuleTable,
                zones: ZoneTable) -> PackedTables:
    cols = jnp.stack([getattr(registry, f).astype(jnp.int32)
                      for f in REG_I])                       # [8, D]
    g = registry_group(registry.capacity)
    return PackedTables(
        reg_i=cols.reshape(len(REG_I), -1, g).transpose(
            1, 0, 2).reshape(-1, len(REG_I) * g),
        rules_i=jnp.stack([getattr(rules, f).astype(jnp.int32)
                           for f in RULE_I]),
        rules_f=rules.threshold,
        taus=rules.ewma_tau_s,
        zones_i=jnp.stack([getattr(zones, f).astype(jnp.int32)
                           for f in ZONE_I]),
        zones_v=zones.verts,
    )


def unpack_tables(t: PackedTables) -> Tuple[Registry, RuleTable, ZoneTable]:
    """The tables back as columns (tests and tools; the step never
    unpacks the registry — it looks rows up with ``t.rows_at``)."""
    cols = t.reg_i.reshape(-1, len(REG_I), t._group).transpose(
        1, 0, 2).reshape(len(REG_I), -1)
    ri = {f: cols[i] for i, f in enumerate(REG_I)}
    ri["active"] = ri["active"] != 0
    registry = Registry(epoch=jnp.int32(0), **ri)
    return (registry, *_unpack_rule_tables(t))


def _unpack_rule_tables(t: PackedTables) -> Tuple[RuleTable, ZoneTable]:
    li = {f: t.rules_i[i] for i, f in enumerate(RULE_I)}
    li["active"] = li["active"] != 0
    rules = RuleTable(threshold=t.rules_f, ewma_tau_s=t.taus, **li)
    zi = {f: t.zones_i[i] for i, f in enumerate(ZONE_I)}
    zi["active"] = zi["active"] != 0
    zones = ZoneTable(verts=t.zones_v, **zi)
    return rules, zones


PACK_BLOCK = 1 << 16   # devices packed at a time (bounds pack_state's temps)


def pack_state(state: DeviceState) -> PackedState:
    """The carry from its columns, :data:`PACK_BLOCK` devices at a time,
    each block written into the carry in place.  Laying a ``[D]`` column
    into a lane makes a ``[D, 1]`` sliver that the chip pads to 128 lanes:
    packed in one piece, a registry holds a carry-sized temporary per
    column (38 GB at 1<<22 slots, and the chip's compiler turns every
    whole-array transpose into just that); a block's slivers are 32 MB."""
    M, K = state.num_mtype_slots, state.num_ewma_scales
    D = state.capacity
    W = packed_row_width(M, K)
    blk = PACK_BLOCK if D % PACK_BLOCK == 0 else D
    named = [_bits(getattr(state, f)) for f in STATE_LANES]
    meas = [state.last_value_ts_s, state.last_value_ts_ns,
            _bits(state.last_values),
            _bits(state.ewma_values).transpose(0, 2, 1).reshape(D, K * M)]

    def pack_block(i, rows):
        def block(x):
            return jax.lax.dynamic_slice_in_dim(x, i * blk, blk)

        return jax.lax.dynamic_update_slice_in_dim(rows, jnp.concatenate([
            jnp.stack([block(c) for c in named], axis=1),
            jnp.zeros((blk, MEAS_LANE - len(STATE_LANES)), jnp.int32),
            *[block(x) for x in meas],
            jnp.zeros((blk, W - MEAS_LANE - M * (3 + K)), jnp.int32),
        ], axis=1), i * blk, axis=0)

    rows = jax.lax.fori_loop(
        0, D // blk, pack_block, jnp.zeros((D, W), jnp.int32))
    return PackedState(rows=rows, num_mtype_slots=M, num_ewma_scales=K)


def unpack_state(ps: PackedState) -> DeviceState:
    M, K = ps.num_mtype_slots, ps.num_ewma_scales
    lanes = ps.rows.T                                        # [W, D]
    cols = {}
    for f in STATE_LANES:
        c = lanes[_LANE[f]]
        cols[f] = _floats(c) if f in STATE_FLOAT else c
    cols["presence_missing"] = cols["presence_missing"] != 0

    def meas(j, n=1):
        return lanes[MEAS_LANE + j * M:MEAS_LANE + (j + n) * M]

    return DeviceState(
        last_value_ts_s=meas(0).T,
        last_value_ts_ns=meas(1).T,
        last_values=_floats(meas(2)).T,
        ewma_values=_floats(meas(3, K)).reshape(K, M, -1).transpose(2, 1, 0),
        **cols,
    )


def unpack_batch(bi: jax.Array, bf: jax.Array) -> EventBatch:
    cols = {f: bi[i] for i, f in enumerate(BATCH_I)}
    cols["valid"] = cols["valid"] != 0
    cols["update_state"] = cols["update_state"] != 0
    return EventBatch(**cols, **{f: bf[i] for i, f in enumerate(BATCH_F)})


def pack_outputs(out: PipelineOutputs,
                 batch: Optional[EventBatch] = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """PipelineOutputs → (oi [10, B] int32, metrics [n] int32, present[D]).

    The metrics vector is the step scalars + per-type counts + the
    :data:`TELEMETRY_SCALARS` occupancy block + the per-tenant
    :data:`TENANT_METER_COUNTERS` scatter block (all computed on device
    from outputs the step already materialized — a handful of fused
    reductions plus one segment-sum, free under XLA).  ``batch`` feeds
    the state-write count (``accepted & update_state`` is the mask
    ``update_device_state`` applies) and the tenant bucketing; without
    it state_writes degrades to the accepted count and the tenant block
    is zeros (legacy single-output callers).
    """
    derived = out.derived_alerts
    flags = (out.accepted * F_ACCEPTED
             + out.unregistered * F_UNREGISTERED
             + out.unassigned * F_UNASSIGNED
             + derived.valid * F_DERIVED).astype(jnp.int32)
    oi = jnp.stack([
        flags, out.device_type_id, out.assignment_id, out.area_id,
        out.customer_id, out.asset_id, out.rule_id, out.zone_id,
        derived.alert_code, derived.alert_level,
    ])
    m = out.metrics
    width = out.accepted.shape[0]
    writes = out.accepted
    if batch is not None:
        writes = writes & batch.update_state
    telemetry = jnp.stack([
        jnp.int32(width) - m.processed,                  # rows_invalid
        writes.sum(dtype=jnp.int32),                     # state_writes
        out.present_now.sum(dtype=jnp.int32),            # presence_merges
        out.nonfinite.sum(dtype=jnp.int32),              # rows_nonfinite
    ])
    if batch is not None:
        # Per-tenant block: bucket rows by tenant hash and scatter-add
        # the three masked counts in ONE segment-sum ([B, 3] data over
        # [B] segment ids → [T, 3]).  jnp's mod keeps negative ids
        # (NULL_ID padding) in range; padded rows carry all-False masks
        # so they contribute zeros wherever they land.
        bucket = batch.tenant_id.astype(jnp.int32) % TENANT_METER_SLOTS
        counts = jnp.stack([
            out.accepted, writes, out.nonfinite,
        ], axis=-1).astype(jnp.int32)                    # [B, 3]
        per_tenant = jax.ops.segment_sum(
            counts, bucket, num_segments=TENANT_METER_SLOTS)
        tenant_block = per_tenant.T.reshape(-1)          # counter-major
    else:
        tenant_block = jnp.zeros((TENANT_METER_BLOCK,), jnp.int32)
    metrics = jnp.concatenate([
        jnp.stack([getattr(m, f) for f in METRIC_SCALARS]), m.by_type,
        telemetry, tenant_block])
    return oi, metrics, out.present_now


def packed_pipeline_step(
    tables: PackedTables, ps: PackedState, bi: jax.Array, bf: jax.Array
) -> Tuple[PackedState, jax.Array, jax.Array, jax.Array]:
    """The fused step over the packed interface (semantics identical to
    :func:`pipeline_step`; jit with ``donate_argnums=(1,)``): the same
    :func:`~sitewhere_tpu.pipeline.step.fused_step`, gathering from and
    scattering into the packed carry — the carry is never unpacked."""
    rules, zones = _unpack_rule_tables(tables)
    batch = unpack_batch(bi, bf)
    new_ps, out = fused_step(tables, ps, rules, zones, batch)
    return new_ps, *pack_outputs(out, batch)


def build_packed_step(donate: bool) -> Callable:
    """The single packed step, jitted.  ``donate=True`` donates the
    carry (slot 1) so the scatter writes the rows in place instead of
    into a copy of the whole carry (1.63 ms of a 1.85 ms step at 1<<20
    slots): the caller hands over the state manager's epoch under its
    lock (``DeviceStateManager.step_packed``).  The dispatcher passes
    ``donate=False`` on the CPU backend, as for the chain."""
    return jax.jit(packed_pipeline_step,
                   donate_argnums=(1,) if donate else ())


def build_packed_chain(k: int, donate: bool = True) -> Callable:
    """K packed steps chained in ONE compiled program — the device-resident
    dispatch loop's kernel.

    The returned jitted callable takes ``(tables, ps, *slots)`` where
    ``slots`` is K staged ``bi`` arrays followed by K staged ``bf`` arrays
    (the ring's pre-staged input slots, H2D'd ahead of time by
    :func:`stage_packed_batch`).  A ``lax.fori_loop`` cycles the slots
    through :func:`packed_pipeline_step`, threading the ``PackedState``
    carry on device, so the host pays ONE dispatch — and later one D2H
    fetch — per K steps instead of per step.

    Returns ``(ps', ois [K, 10, B], metrics [K, 12], present [D])``:
    per-step output blocks stacked along a leading slot axis (egress
    slices its step's block from one shared fetch) and ``present`` the
    OR over the chain's per-step presence maps — the devices this chain
    merged, which is exactly what the state manager's presence
    reconciliation needs at chain granularity.

    ``donate=True`` donates the carry (slot 1): the caller must own the
    buffers exclusively (``DeviceStateManager.lease_packed``).  The
    dispatcher passes ``donate=False`` on the CPU backend, where a carry
    is cheap to copy and tests hold on to their epochs.
    """
    def chain(tables, ps, *slots):
        return chain_over_slots(packed_pipeline_step, k, tables, ps, slots)

    return jax.jit(chain, donate_argnums=(1,) if donate else ())


def packed_metric_entries() -> int:
    """Length of the packed metrics vector (one authority for builders)."""
    from sitewhere_tpu.pipeline.step import NUM_EVENT_TYPES

    return (len(METRIC_SCALARS) + NUM_EVENT_TYPES + len(TELEMETRY_SCALARS)
            + TENANT_METER_BLOCK)


def chain_over_slots(step, k: int, tables, ps, slots):
    """The K-step fori_loop core shared by the single-chip and the
    sharded (``shard_map`` local-body) chains: cycle the K pre-staged
    ``(bi, bf)`` slots through ``step`` threading the ``PackedState``
    carry on device, stacking per-step output blocks along a leading
    slot axis and OR-ing presence over the chain.

    ``step`` has the :func:`packed_pipeline_step` signature; the sharded
    builder passes its id-offsetting local step instead.  Returns
    ``(ps', ois [K, 10, B], metrics [K, n], present [D])``.
    """
    n_out = len(OUT_I)
    n_met = packed_metric_entries()
    ring_i = jnp.stack(slots[:k])   # [K, 12, B]
    ring_f = jnp.stack(slots[k:])   # [K, 4, B]
    width = ring_i.shape[-1]

    def body(i, carry):
        c, ois, mets, present = carry
        bi = jax.lax.dynamic_index_in_dim(ring_i, i, keepdims=False)
        bf = jax.lax.dynamic_index_in_dim(ring_f, i, keepdims=False)
        c, oi, met, pres = step(tables, c, bi, bf)
        ois = jax.lax.dynamic_update_index_in_dim(ois, oi, i, 0)
        mets = jax.lax.dynamic_update_index_in_dim(mets, met, i, 0)
        return c, ois, mets, present | pres

    init = (
        ps,
        jnp.zeros((k, n_out, width), jnp.int32),
        jnp.zeros((k, n_met), jnp.int32),
        jnp.zeros((ps.capacity,), bool),
    )
    return jax.lax.fori_loop(0, k, body, init)


def ring_depth_default() -> int:
    """Backend-adaptive ring depth for the device-resident dispatch loop.

    On TPU chaining 8 steps per dispatch amortizes the per-step host
    sync 8× (what that buys on a co-located chip is ROADMAP S1's
    question: K adds batching delay to every chained event).  On CPU a
    dispatch is a function call — the chain only adds compile time and
    batching delay, so the ring defaults OFF (forcible via
    ``pipeline.ring_depth`` for the tier-1 smoke of the chained path).
    An explicit ``pipeline.ring_depth`` config wins on any backend.
    """
    try:
        return 8 if jax.default_backend() == "tpu" else 0
    except Exception:  # no backend at all
        return 0


def packed_presence_sweep(ps: PackedState, now_s, missing_after_s):
    """Presence sweep over the packed carry: reads three lanes, writes
    one.  The served sweep (``DeviceStateManager.apply_presence_sweep``)
    jits it without donation, so it pays the carry's copy; with
    ``donate_argnums=(0,)`` it is in place (ROADMAP S12)."""
    from sitewhere_tpu.state.presence import newly_missing

    with jax.named_scope("presence_sweep"):
        rows = ps.rows
        missing = rows[:, PRESENCE_LANE] != 0
        newly = newly_missing(
            rows[:, _LANE["last_event_type"]],
            rows[:, _LANE["last_event_ts_s"]],
            missing, now_s, missing_after_s)
        rows = rows.at[:, PRESENCE_LANE].set(
            (missing | newly).astype(rows.dtype))
    return ps.replace(rows=rows), newly


# -- host side --------------------------------------------------------------

_BATCH_STAGING: Optional[bool] = None   # supports_batch_staging's memo


# Unexpected async-copy failures (anything that is NOT the benign
# deleted/donated-buffer race).  The copy itself is an optimization — the
# blocking fetch still lands the bytes — but a backend refusing the
# async form is a capability regression an operator must be able to see,
# not a silent fall-back to one-RTT-per-fetch behavior.
host_copy_errors = 0


def _is_deleted_buffer_error(e: BaseException) -> bool:
    """The ONE benign async-copy failure: the array was deleted/donated
    between dispatch and the copy call (a later step's donation won the
    race).  Everything else is unexpected and must be counted."""
    s = str(e).lower()
    return "delete" in s or "donat" in s


def start_host_copy(*arrays, on_error: Optional[Callable] = None) -> None:
    """Kick off async device→host copies: by the time egress blocks on
    ``np.asarray`` the bytes are host-side.

    Only the deleted/donated-buffer race is swallowed silently; any other
    failure increments :data:`host_copy_errors`, logs, and calls
    ``on_error(exc)`` (the dispatcher wires a metric counter) — then the
    remaining arrays still get their copies attempted."""
    global host_copy_errors
    for dev in arrays:
        fn = getattr(dev, "copy_to_host_async", None)
        if fn is None:
            continue  # committed host / numpy array — nothing to copy
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — classified below
            if isinstance(e, RuntimeError) and _is_deleted_buffer_error(e):
                continue
            host_copy_errors += 1
            logger.warning("async host copy failed (%s): %s",
                           type(e).__name__, e)
            if on_error is not None:
                on_error(e)


def supports_batch_staging() -> bool:
    """Once-probed: is ahead-of-step ``device_put`` staging a win?  Only
    off the CPU backend — there device_put is a synchronous memcpy, so
    staging would add a copy without overlapping anything."""
    global _BATCH_STAGING
    if _BATCH_STAGING is None:
        try:
            _BATCH_STAGING = jax.default_backend() != "cpu"
        except Exception:
            _BATCH_STAGING = False
    return _BATCH_STAGING


def stage_packed_batch(bi: np.ndarray, bf: np.ndarray,
                       force: bool = False):
    """Start the H2D transfer of one packed batch ahead of its step (the
    double-buffer front half): ``device_put`` returns immediately with
    arrays whose transfer proceeds asynchronously, so staging plan N+1
    while plan N computes overlaps the copy with the step.  Returns None
    when staging is unsupported (sync fallback: the jitted call moves the
    numpy buffers itself, exactly the pre-staging behavior)."""
    if not (force or supports_batch_staging()):
        return None
    try:
        return jax.device_put(bi), jax.device_put(bf)
    except Exception:  # backend refused — fall back to sync transfer
        return None


def pack_batch_host(cols: Dict[str, np.ndarray],
                    width: int) -> Tuple[np.ndarray, np.ndarray]:
    """Numpy columns → ([12, B] int32, [4, B] float32), one memcpy each."""
    bi = np.empty((len(BATCH_I), width), np.int32)
    bf = np.empty((len(BATCH_F), width), np.float32)
    for i, f in enumerate(BATCH_I):
        bi[i] = cols[f]
    for i, f in enumerate(BATCH_F):
        bf[i] = cols[f]
    return bi, bf


def _blocking_fetch(dev_pair, wait_timer=None, seq: int = -1):
    """THE blocking device→host fetch of a step's (or a ring's) output
    pair: one ``device_get`` — it starts the copies for every leaf
    before blocking on any, so the pair costs one host sync even when
    the dispatch-time ``copy_to_host_async`` has not landed yet.  Under
    ``wait_timer`` (the dispatcher's ``pipeline.device_wait_s``) the
    wait is a timer observation and a profiler span tagged ``seq``: the
    host blocked on the device finishing the step and on D2H."""
    with (wait_timer.time(seq=seq) if wait_timer is not None
          else contextlib.nullcontext()):
        a, b = jax.device_get(dev_pair)
        return np.asarray(a), np.asarray(b)


class PackedView:
    """Host-side adapter over the packed step outputs.

    Duck-types the slice of :class:`PipelineOutputs` the dispatcher's
    egress consumes, fetching the [10, B] output block ONCE (one transfer)
    and exposing columns as numpy views.  ``present_now`` stays a device
    array — it feeds the next commit, never the host.
    """

    def __init__(self, oi, metrics, present_now, on_fetch=None,
                 wait_timer=None, seq: int = -1):
        self._oi_dev = oi
        self._metrics_dev = metrics
        self.present_now = present_now
        self._oi = None
        self._metrics = None
        self._metrics_host = None
        self._accepted = None
        # host-sync instrumentation: called ONCE, at the blocking fetch
        # (the dispatcher wires its ``pipeline.host_syncs`` counter)
        self._on_fetch = on_fetch
        # ... and timed there (``pipeline.device_wait_s``), tagged with
        # the plan's ``seq``
        self._wait_timer = wait_timer
        self._seq = seq

    def _fetch(self) -> None:
        """Materialize BOTH host copies in one :func:`_blocking_fetch`."""
        if self._on_fetch is not None:
            self._on_fetch()
        self._oi, self._metrics_host = _blocking_fetch(
            (self._oi_dev, self._metrics_dev), self._wait_timer, self._seq)

    @property
    def oi(self) -> np.ndarray:
        if self._oi is None:
            self._fetch()
        return self._oi

    def _row(self, name: str) -> np.ndarray:
        return self.oi[OUT_I.index(name)]

    @property
    def accepted(self) -> np.ndarray:
        # memoized against the fetched block: egress consults the mask
        # several times per plan (store/outbound/analytics/command
        # routing), and the ring's shared fetch should materialize it
        # once per slot, not once per consumer
        a = self._accepted
        if a is None:
            a = self._accepted = (self._row("flags") & F_ACCEPTED) != 0
        return a

    @property
    def unregistered(self) -> np.ndarray:
        return (self._row("flags") & F_UNREGISTERED) != 0

    @property
    def unassigned(self) -> np.ndarray:
        return (self._row("flags") & F_UNASSIGNED) != 0

    @property
    def derived_valid(self) -> np.ndarray:
        return (self._row("flags") & F_DERIVED) != 0

    def __getattr__(self, name):
        if name in OUT_I:
            return self._row(name)
        raise AttributeError(name)

    @property
    def metrics(self) -> StepMetrics:
        if self._metrics is None:
            if self._metrics_host is None:
                self._fetch()
            v = self._metrics_host
            n = len(METRIC_SCALARS)
            self._metrics = StepMetrics(
                by_type=v[n:n + NUM_EVENT_TYPES],
                **{f: v[i] for i, f in enumerate(METRIC_SCALARS)})
        return self._metrics

    @property
    def telemetry(self) -> Dict[str, int]:
        """The on-device occupancy block (``TELEMETRY_SCALARS``), read
        from the SAME fetched metrics vector the step metrics ride —
        never an extra sync.  Empty for pre-telemetry vectors (tests
        that stub a bare 12-wide metrics array)."""
        if self._metrics_host is None:
            self._fetch()
        v = self._metrics_host
        base = len(METRIC_SCALARS) + NUM_EVENT_TYPES
        if len(v) < base + len(TELEMETRY_SCALARS):
            return {}
        return {f: int(v[base + i])
                for i, f in enumerate(TELEMETRY_SCALARS)}

    @property
    def tenant_meter(self) -> Optional[np.ndarray]:
        """The per-tenant attribution block as ``[len(
        TENANT_METER_COUNTERS), TENANT_METER_SLOTS]`` int — sliced from
        the SAME fetched metrics vector (never an extra sync).  None for
        pre-metering vectors (stubs/legacy captures), mirroring how
        :attr:`telemetry` degrades to ``{}``."""
        if self._metrics_host is None:
            self._fetch()
        v = self._metrics_host
        base = len(METRIC_SCALARS) + NUM_EVENT_TYPES + len(TELEMETRY_SCALARS)
        if len(v) < base + TENANT_METER_BLOCK:
            return None
        return np.asarray(v[base:base + TENANT_METER_BLOCK]).reshape(
            len(TENANT_METER_COUNTERS), TENANT_METER_SLOTS)

    def derived_cols(self, host_cols: Dict[str, np.ndarray],
                     rows: np.ndarray) -> Dict[str, np.ndarray]:
        """Reconstruct the derived-alert event columns for ``rows`` from
        the original host columns + the packed outputs (mirrors
        ``_build_derived_alerts`` without round-tripping a full
        same-width EventBatch off the device)."""
        from sitewhere_tpu.schema import EventType

        n = rows.size
        return dict(
            device_id=host_cols["device_id"][rows],
            tenant_id=host_cols["tenant_id"][rows],
            event_type=np.full(n, int(EventType.ALERT), np.int32),
            ts_s=host_cols["ts_s"][rows],
            ts_ns=host_cols["ts_ns"][rows],
            alert_code=self._row("derived_code")[rows],
            alert_level=self._row("derived_level")[rows],
            payload_ref=host_cols["payload_ref"][rows],
            update_state=np.zeros(n, bool),
        )


class RingFetch:
    """ONE D2H fetch shared by every step view of a chained dispatch.

    The packed chain returns the whole ring's outputs stacked
    (``ois [K, 10, B]``, ``metrics [K, 16]``); the first step view that
    egress touches blocks on a single ``device_get`` for the pair, and
    every sibling slot reads its slice from the same host copy — K steps,
    one host sync.  The copies were started asynchronously at dispatch
    (:func:`start_host_copy`), so in steady state the blocking fetch
    finds the bytes already host-side.
    """

    def __init__(self, ois, metrics, on_fetch=None, wait_timer=None,
                 seq: int = -1):
        self._ois_dev = ois
        self._metrics_dev = metrics
        self._host: Optional[tuple] = None
        self._on_fetch = on_fetch
        self._wait_timer = wait_timer
        self._seq = seq   # the ring's first plan

    def fetch(self) -> tuple:
        if self._host is None:
            if self._on_fetch is not None:
                self._on_fetch()
            self._host = _blocking_fetch(
                (self._ois_dev, self._metrics_dev), self._wait_timer,
                self._seq)
        return self._host


class RingStepView(PackedView):
    """One chained step's :class:`PackedView`, backed by the ring's
    shared fetch — slot ``k``'s ``[10, B]`` block and ``[16]`` metrics
    row sliced from the stacked host copy.  ``present_now`` is None:
    presence commits at chain granularity (the chain's OR'd map), never
    per slot."""

    def __init__(self, ring: RingFetch, slot: int):
        super().__init__(None, None, None)
        self._ring_fetch = ring
        self.slot = slot

    def _fetch(self) -> None:
        ois, mets = self._ring_fetch.fetch()
        self._oi = ois[self.slot]
        self._metrics_host = mets[self.slot]


__all__ = [
    "PackedTables", "PackedState", "PackedView",
    "RingFetch", "RingStepView",
    "pack_tables", "unpack_tables", "pack_state", "unpack_state",
    "unpack_batch", "pack_outputs", "packed_pipeline_step",
    "build_packed_chain", "chain_over_slots", "packed_metric_entries",
    "ring_depth_default",
    "pack_batch_host", "stage_packed_batch", "start_host_copy",
    "supports_batch_staging",
    "F_ACCEPTED", "F_UNREGISTERED", "F_UNASSIGNED", "F_DERIVED",
    "BATCH_I", "BATCH_F", "OUT_I", "PRESENCE_LANE",
    "METRIC_SCALARS", "TELEMETRY_SCALARS",
    "TENANT_METER_COUNTERS", "TENANT_METER_SLOTS", "TENANT_METER_BLOCK",
]

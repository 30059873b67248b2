"""SPMD pipeline over a device mesh — the Kafka-partitioning analog.

The reference scales the pipeline horizontally by partitioning Kafka topics
on device token (``MicroserviceKafkaProducer.java:106``) and running one
consumer-group member per partition set (``KafkaRuleProcessorHost.java:78-80``).
Here the same decomposition is a ``shard_map`` over the ``shard`` mesh axis:

- registry + state tensors are block-sharded along device capacity;
- the host batcher routes each event into the sub-batch of the shard that
  owns its registry row (:func:`sitewhere_tpu.parallel.mesh.shard_for_device`),
  so validation/enrichment gathers are strictly shard-local — zero ICI
  traffic on the hot path;
- rules + zones are replicated (small broadcast tables, the analog of each
  consumer holding its own rule/zone cache);
- metrics are ``psum``-ed over the shard axis so the host sees one global
  counter set (the analog of the aggregated Dropwizard metrics).

A mis-routed event (its device row lives on another shard) cannot be
validated locally and is reported ``unregistered`` — the host dead-letter
path re-routes it, mirroring how the reference replays events that hit a
stale consumer after a rebalance.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sitewhere_tpu.parallel.mesh import SHARD_AXIS
from sitewhere_tpu.parallel.shmap import shard_map
from sitewhere_tpu.pipeline.step import PipelineOutputs, StepMetrics, pipeline_step
from sitewhere_tpu.schema import (
    DeviceState,
    EventBatch,
    Registry,
    RuleTable,
    ZoneTable,
)


def _specs_sharded(tree) -> object:
    """P(shard) on the leading axis of every array leaf; scalars replicated."""
    return jax.tree_util.tree_map(
        lambda x: P() if jnp.ndim(x) == 0 else P(SHARD_AXIS, *([None] * (jnp.ndim(x) - 1))),
        tree,
    )


def _specs_replicated(tree) -> object:
    return jax.tree_util.tree_map(lambda x: P(), tree)


def build_sharded_step(mesh: Mesh, donate: bool = True):
    """Build the jitted multi-chip pipeline step for ``mesh``.

    Returns ``step(registry, state, rules, zones, batch) -> (state, outputs)``
    operating on globally-sharded arrays (place inputs with
    :func:`place_inputs` or equivalent ``device_put``).  Not served: the
    dispatcher runs :func:`build_sharded_packed_step`.  This unpacked form,
    with :func:`place_inputs` and :func:`place_batch`, is the reference
    the sharded packed programs are compared against in tests.

    ``donate=False`` keeps the input state buffers alive for a caller
    that reads the previous epoch after the step.
    """
    reg_t = Registry.empty(8)
    state_t = DeviceState.empty(8)
    rules_t = RuleTable.empty(1)
    zones_t = ZoneTable.empty(1, max_verts=4)
    batch_t = EventBatch.empty(8)

    in_specs = (
        _specs_sharded(reg_t),
        _specs_sharded(state_t),
        _specs_replicated(rules_t),
        _specs_replicated(zones_t),
        _specs_sharded(batch_t),
    )
    # Derive outputs specs from a template so new PipelineOutputs fields
    # inherit row-level sharding automatically; only metrics (psum-ed
    # global counters) are replicated.
    metrics_t = StepMetrics(
        processed=jnp.int32(0), accepted=jnp.int32(0), unregistered=jnp.int32(0),
        unassigned=jnp.int32(0), threshold_alerts=jnp.int32(0),
        zone_alerts=jnp.int32(0), by_type=jnp.zeros(6, jnp.int32),
    )
    outputs_t = PipelineOutputs(
        accepted=jnp.zeros(8, bool), unregistered=jnp.zeros(8, bool),
        unassigned=jnp.zeros(8, bool), nonfinite=jnp.zeros(8, bool),
        device_type_id=jnp.zeros(8, jnp.int32),
        assignment_id=jnp.zeros(8, jnp.int32), area_id=jnp.zeros(8, jnp.int32),
        customer_id=jnp.zeros(8, jnp.int32), asset_id=jnp.zeros(8, jnp.int32),
        rule_id=jnp.zeros(8, jnp.int32), zone_id=jnp.zeros(8, jnp.int32),
        present_now=jnp.zeros(8, bool),
        derived_alerts=batch_t, metrics=metrics_t,
    )
    out_specs = (
        _specs_sharded(state_t),
        _specs_sharded(outputs_t).replace(metrics=_specs_replicated(metrics_t)),
    )

    def local_step(registry, state, rules, zones, batch):
        # Global device id -> local registry row on this shard.
        rows_local = registry.capacity
        offset = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int32) * rows_local
        local_ids = jnp.where(batch.device_id >= 0, batch.device_id - offset, -1)
        # Foreign rows fall outside [0, rows_local) and are reported
        # unregistered by validate_and_enrich's range check.
        local_batch = batch.replace(device_id=local_ids)

        new_state, out = pipeline_step(registry, state, rules, zones, local_batch)

        # Restore global ids in row-level outputs.
        derived = out.derived_alerts
        derived = derived.replace(
            device_id=jnp.where(derived.device_id >= 0, derived.device_id + offset,
                                derived.device_id)
        )
        with jax.named_scope("mesh_reduce"):
            metrics = jax.tree_util.tree_map(
                lambda c: jax.lax.psum(c, SHARD_AXIS), out.metrics
            )
        out = out.replace(derived_alerts=derived, metrics=metrics)
        return new_state, out

    mapped = shard_map(
        local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(1,) if donate else ())


def _local_packed_step(tables, ps, bi, bf):
    """One shard's packed step inside ``shard_map``: global device ids
    become local registry rows (foreign rows fall outside ``[0,
    rows_local)`` and are reported unregistered by the range check),
    then the single-chip :func:`packed_pipeline_step` runs on the
    shard's block of the carry.  Derived-alert/enrich ids in ``oi`` are
    table indices (replicated tables → already global); device ids never
    leave the host columns."""
    from sitewhere_tpu.pipeline.packed import BATCH_I, packed_pipeline_step

    row = BATCH_I.index("device_id")
    rows_local = ps.capacity
    offset = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int32) * rows_local
    ids = bi[row]
    bi = bi.at[row].set(jnp.where(ids >= 0, ids - offset, -1))
    return packed_pipeline_step(tables, ps, bi, bf)


def build_sharded_packed_step(mesh: Mesh):
    """The packed interface over the mesh (the multi-chip deployment
    form): same local-step semantics as :func:`build_sharded_step`, but
    the per-step host surface is the packed buffer set — batch crosses
    as ``[12, B] + [4, B]`` sharded on axis 1, state rides as one
    buffer of device rows sharded on axis 0, outputs as one ``[10, B]``
    block + psum-ed metrics.  Per-call placement cost on a mesh scales
    with buffer count × hosts, so this is the packed step's ~10× buffer
    reduction where it matters most.  NO donation: the carry is the
    state manager's live epoch.
    """
    in_specs = (_packed_tables_specs(), _PACKED_STATE_SPEC,
                _PACKED_BATCH_SPEC, _PACKED_BATCH_SPEC)
    out_specs = (_PACKED_STATE_SPEC, _PACKED_BATCH_SPEC, P(), P(SHARD_AXIS))

    def local_step(tables, ps, bi, bf):
        new_ps, oi, metrics, present = _local_packed_step(tables, ps, bi, bf)
        # telemetry rides the psum-ed metrics vector: occupancy counters
        # aggregate over shards exactly like the step scalars
        with jax.named_scope("mesh_reduce"):
            metrics = jax.lax.psum(metrics, SHARD_AXIS)
        return new_ps, oi, metrics, present

    mapped = shard_map(
        local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(mapped)


def build_sharded_packed_chain(mesh: Mesh, k: int, donate: bool = True):
    """The K-deep packed chain running SPMD over the mesh — the fusion
    of :func:`sitewhere_tpu.pipeline.packed.build_packed_chain` (host
    syncs 1/K) with :func:`build_sharded_packed_step` (device-state,
    dedup and presence sharded by device-id).

    Same layout authority as the single step — ``_packed_tables_specs``
    for the resident tables, :data:`_PACKED_STATE_SPEC` for the carry
    and :data:`_PACKED_BATCH_SPEC` for every staged batch slot — so
    host-side placement (:func:`place_packed_batch` /
    :func:`place_packed_state`) feeds both paths identically.  Inside
    the ``shard_map`` body the local chain is
    :func:`~sitewhere_tpu.pipeline.packed.chain_over_slots` over the
    id-offsetting local step; rule eval stays data-parallel (rule/zone
    tables are replicated, so no gather crosses shards — the all-gather
    hook only matters once rules reference foreign-device state).  The
    stacked per-step metrics are ``psum``-ed ONCE per chain — K steps,
    one collective, exactly the per-step psum summed over the chain.

    Returns ``(ps', ois [K, 10, B], metrics [K, n], present [D])`` with
    ``ois`` width-sharded, metrics replicated, ``present`` block-sharded
    by capacity.  ``donate=True`` donates the state carry: the mesh ring
    runs on a ``DeviceStateManager.lease_packed`` exclusive hand-off, so
    unlike :func:`build_sharded_packed_step` (which steps the live
    epoch) the chain may consume its input buffer.
    """
    from sitewhere_tpu.pipeline.packed import chain_over_slots

    in_specs = (_packed_tables_specs(), _PACKED_STATE_SPEC) + (
        _PACKED_BATCH_SPEC,) * (2 * k)
    out_specs = (_PACKED_STATE_SPEC, P(None, None, SHARD_AXIS), P(),
                 P(SHARD_AXIS))

    def local_chain(tables, ps, *slots):
        c, ois, mets, present = chain_over_slots(
            _local_packed_step, k, tables, ps, slots)
        # one collective per chain: psum of the stacked [K, n] block is
        # the per-step psum the single sharded step would have done K×
        with jax.named_scope("mesh_reduce"):
            mets = jax.lax.psum(mets, SHARD_AXIS)
        return c, ois, mets, present

    mapped = shard_map(
        local_chain, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(1,) if donate else ())


# The packed-mesh sharding layout lives HERE, once: the shard_map specs
# and every host-side placement read these, so they cannot drift.
_PACKED_STATE_SPEC = P(SHARD_AXIS)         # device rows, by capacity
_PACKED_BATCH_SPEC = P(None, SHARD_AXIS)   # [C, B] columns, by width


def _packed_tables_specs():
    from sitewhere_tpu.pipeline.packed import PackedTables

    return PackedTables(
        reg_i=P(SHARD_AXIS),   # registry rows shard by capacity
        rules_i=P(), rules_f=P(), taus=P(),   # small broadcast tables
        zones_i=P(), zones_v=P(),
    )


def place_packed_batch(mesh: Mesh, bi, bf):
    """Device-put one packed wire batch sharded along its width axis."""
    s = NamedSharding(mesh, _PACKED_BATCH_SPEC)
    return jax.device_put(bi, s), jax.device_put(bf, s)


def place_packed_tables(mesh: Mesh, t):
    """Device-put a PackedTables with its canonical mesh shardings."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        t, _packed_tables_specs())


def place_packed_state(mesh: Mesh, ps):
    """Device-put a PackedState sharded by capacity (no-op once the
    epoch already carries the sharding, i.e. after the first step)."""
    return ps.replace(rows=jax.device_put(
        ps.rows, NamedSharding(mesh, _PACKED_STATE_SPEC)))


def place_inputs(
    mesh: Mesh,
    registry: Registry,
    state: DeviceState,
    rules: RuleTable,
    zones: ZoneTable,
) -> Tuple[Registry, DeviceState, RuleTable, ZoneTable]:
    """Device-put the resident tables with their canonical shardings
    (for :func:`build_sharded_step`, the tests' reference; not served)."""

    def put(tree, specs):
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
        )

    return (
        put(registry, _specs_sharded(registry)),
        put(state, _specs_sharded(state)),
        put(rules, _specs_replicated(rules)),
        put(zones, _specs_replicated(zones)),
    )


def place_batch(mesh: Mesh, batch: EventBatch) -> EventBatch:
    """Device-put an event batch sharded along its width (for
    :func:`build_sharded_step`, the tests' reference; not served)."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(SHARD_AXIS))), batch
    )

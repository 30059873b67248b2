"""The fused inbound pipeline step.

One jitted function replaces the reference's per-event journey across four
microservices and three Kafka hops (SURVEY.md §3.2):

1. *validate + enrich* — the per-event device/assignment gRPC lookups of
   ``service-inbound-processing/.../InboundPayloadProcessingLogic.java:148-219``
   and the context build of ``OutboundPayloadEnrichmentLogic.java:54-88``
   become registry gathers.
2. *rule evaluation* — ``service-rule-processing``'s per-event callbacks
   (``spi/IRuleProcessor.java:50-97``, ``ZoneTestRuleProcessor.java:32-70``)
   become dense ``[B, R]`` comparisons and a ``[B, Z]`` geofence kernel.
3. *state materialization* — ``service-device-state``'s per-record merge
   (``DeviceStateProcessingLogic.java:46-80``) becomes time-ordered scatters.

Dead-letter routing (unregistered / unassigned events → Kafka topics in
``InboundPayloadProcessingLogic.java:228-247``) comes out as boolean masks
the host journal uses to divert rows.  Derived alert events (the reference
fires them back through event management, ``ZoneTestRuleProcessor.java:60``)
come out as a same-width :class:`EventBatch` ready for re-injection.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.ops.geo_pallas import points_in_polygons_auto
from sitewhere_tpu.ops.scatter import (
    apply_winners,
    bincount_fixed,
    winner_rows,
)
from sitewhere_tpu.schema import (
    DEFAULT_EWMA_TAUS,
    AssignmentStatus,
    ComparisonOp,
    DeviceState,
    EventBatch,
    EventType,
    Registry,
    RuleKind,
    RuleTable,
    ZoneCondition,
    ZoneTable,
)

NUM_EVENT_TYPES = 6


@struct.dataclass
class StepMetrics:
    """Per-step counters — the analog of the Dropwizard meters on the
    reference hot path (``InboundPayloadProcessingLogic.java:90-97``,
    ``InboundEventSource.java:79-81``)."""

    processed: jax.Array          # int32[] — valid rows seen
    accepted: jax.Array           # int32[] — passed validation
    unregistered: jax.Array       # int32[] — unknown device (dead-letter)
    unassigned: jax.Array         # int32[] — no active assignment (dead-letter)
    threshold_alerts: jax.Array   # int32[]
    zone_alerts: jax.Array        # int32[]
    by_type: jax.Array            # int32[NUM_EVENT_TYPES] — accepted, by event type

    def __add__(self, other: "StepMetrics") -> "StepMetrics":
        return jax.tree_util.tree_map(lambda a, b: a + b, self, other)


@struct.dataclass
class PipelineOutputs:
    """Everything the host needs from one pipeline step."""

    # Routing masks (dead-letter topics of KafkaTopicNaming.java:48-78):
    accepted: jax.Array      # bool[B]
    unregistered: jax.Array  # bool[B] → auto-registration (SURVEY.md §3.5)
    unassigned: jax.Array    # bool[B]
    # Numeric-integrity mask: valid rows carrying NaN/Inf in value or
    # geo columns.  These rows still persist as history (accepted stays
    # raw — no silent loss) but are masked out of rules, state merge and
    # analytics so a poison value can never enter the carried aggregates.
    nonfinite: jax.Array     # bool[B]
    # Enrichment context (reference IDeviceEventContext):
    device_type_id: jax.Array  # int32[B]
    assignment_id: jax.Array   # int32[B]
    area_id: jax.Array         # int32[B]
    customer_id: jax.Array     # int32[B]
    asset_id: jax.Array        # int32[B]
    # Rule results (first firing rule/zone per event; counts in metrics):
    rule_id: jax.Array         # int32[B] — NULL_ID if none fired
    zone_id: jax.Array         # int32[B] — NULL_ID if none fired
    # Devices this step merged an event into (bool[capacity]) — the
    # presence signal; StateManager.commit uses it to reconcile with a
    # concurrent sweep without re-deriving a scatter.
    present_now: jax.Array
    # Derived alert events ready for re-injection (same width as input):
    derived_alerts: EventBatch
    metrics: StepMetrics


def validate_and_enrich(
    registry: Registry, batch: EventBatch
) -> Tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Registry gather replacing the per-event device/assignment lookups.

    Reference: ``InboundPayloadProcessingLogic.validateAssignment:185-219``
    — device-by-token then assignment lookup over cached gRPC; missing
    device → unregistered dead-letter (``:228-233``), missing/inactive
    assignment → unassigned dead-letter.
    """
    cap = registry.capacity
    ids = batch.device_id
    in_range = (ids >= 0) & (ids < cap)
    safe = jnp.clip(ids, 0, cap - 1)

    # ONE packed [B, 8] gather instead of eight per-column gathers: a
    # [B]-sized gather costs ~1 ms at width 131k on v5e while the packed
    # multi-column form costs barely more than one — the registry is tiny
    # (capacity x 8 int32), so the per-step stack is free.
    packed = jnp.stack(
        [
            registry.active.astype(jnp.int32),
            registry.tenant_id,
            registry.assignment_status,
            registry.device_type_id,
            registry.assignment_id,
            registry.area_id,
            registry.customer_id,
            registry.asset_id,
        ],
        axis=1,
    )[safe]  # [B, 8]

    registered = in_range & (packed[:, 0] != 0)
    # Tenant isolation: an event claiming tenant T must hit a device owned
    # by T (reference: per-tenant engines are shared-nothing slices,
    # MultitenantMicroservice.java:242-260).
    tenant_ok = packed[:, 1] == batch.tenant_id
    assigned = packed[:, 2] == AssignmentStatus.ACTIVE

    valid = batch.valid
    unregistered = valid & ~(registered & tenant_ok)
    unassigned = valid & registered & tenant_ok & ~assigned
    accepted = valid & registered & tenant_ok & assigned

    enrich = {
        "device_type_id": jnp.where(accepted, packed[:, 3], NULL_ID),
        "assignment_id": jnp.where(accepted, packed[:, 4], NULL_ID),
        "area_id": jnp.where(accepted, packed[:, 5], NULL_ID),
        "customer_id": jnp.where(accepted, packed[:, 6], NULL_ID),
        "asset_id": jnp.where(accepted, packed[:, 7], NULL_ID),
    }
    return accepted, unregistered, unassigned, enrich


def _gather_meas_state(
    state: DeviceState, batch: EventBatch
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-row previous measurement-slot state via TWO packed gathers.

    Returns ``(prev_ts, prev_ns, prev_value, ewma_prev[B, K])``.  Packing
    the int columns into ``[D*M, 2]`` and the float columns into
    ``[D*M, 1+K]`` replaces five separate [B]-sized gathers (each ~1 ms at
    width 131k on v5e; multi-column gathers cost barely more than one).
    """
    cap = state.capacity
    M = state.num_mtype_slots
    ids_safe = jnp.clip(batch.device_id, 0, cap - 1)
    slot = jnp.where(batch.mtype_id >= 0, batch.mtype_id % M, 0)
    flat = ids_safe * M + slot
    ipack = jnp.stack(
        [state.last_value_ts_s.reshape(-1), state.last_value_ts_ns.reshape(-1)],
        axis=1,
    )[flat]  # [B, 2]
    fpack = jnp.concatenate(
        [state.last_values.reshape(-1, 1),
         state.ewma_values.reshape(-1, state.num_ewma_scales)],
        axis=1,
    )[flat]  # [B, 1 + K]
    return ipack[:, 0], ipack[:, 1], fpack[:, 0], fpack[:, 1:]


def fold_ewma_arrays(
    prev_ts: jax.Array,
    prev_ns: jax.Array,
    ewma_prev: jax.Array,
    ts_s: jax.Array,
    ts_ns: jax.Array,
    value: jax.Array,
    taus: jax.Array,
) -> jax.Array:
    """Array-level irregular-sampling EWMA fold — the single source of
    the fold math, shared by the fused step and the bring-your-own-rules
    program kernels (``rules/compile.py``), so both lanes stay bitwise
    aligned with the ``rules/interp.py`` golden reference."""
    seeded = prev_ts > 0
    # sub-second resolution: fast sensors sample at > 1 Hz
    dt = jnp.maximum(
        (ts_s - prev_ts).astype(jnp.float32)
        + (ts_ns - prev_ns).astype(jnp.float32) * 1e-9, 0.0)
    alpha = 1.0 - jnp.exp(-dt[:, None] / jnp.maximum(taus[None, :], 1e-9))
    v = value[:, None]
    return jnp.where(seeded[:, None], ewma_prev + alpha * (v - ewma_prev), v)


def _fold_ewma_from(
    prev_ts: jax.Array,
    prev_ns: jax.Array,
    ewma_prev: jax.Array,
    batch: EventBatch,
    taus: jax.Array,
) -> jax.Array:
    """EWMA fold given pre-gathered slot state (see :func:`fold_ewma`)."""
    return fold_ewma_arrays(prev_ts, prev_ns, ewma_prev,
                            batch.ts_s, batch.ts_ns, batch.value, taus)


def fold_ewma(
    state: DeviceState, batch: EventBatch, taus: jax.Array
) -> jax.Array:
    """Per-row candidate EWMAs after folding this row's sample.

    Irregular-sampling EWMA: ``alpha = 1 - exp(-dt / tau)`` with ``dt``
    the gap since the device's previous sample in that measurement slot;
    the first sample seeds the average (no zero bias).  Returns
    ``float32[B, K]`` — rows are CANDIDATES; the time-ordered scatter in
    :func:`update_device_state` picks each slot's winner.
    """
    prev_ts, prev_ns, _, ewma_prev = _gather_meas_state(state, batch)
    return _fold_ewma_from(prev_ts, prev_ns, ewma_prev, batch, taus)


def compare_select(op: jax.Array, val: jax.Array,
                   thr: jax.Array) -> jax.Array:
    """Data-driven :class:`~sitewhere_tpu.schema.ComparisonOp` dispatch.

    A select-chain, NOT a stacked ``[6, ...]`` gather: the stack
    materializes six full result-shaped masks (6x the HBM traffic of
    the compare itself); selects keep one mask live (measured 2.3x on
    [16k, 4k]).  Shared by the built-in rule pass and the
    bring-your-own-rules program kernels, where ``op`` is an operand —
    per-program data, never a compiled shape."""
    return jnp.select(
        [op == ComparisonOp.GT, op == ComparisonOp.LT,
         op == ComparisonOp.GTE, op == ComparisonOp.LTE,
         op == ComparisonOp.EQ],
        [val > thr, val < thr, val >= thr, val <= thr, val == thr],
        default=(val != thr),
    )


def eval_threshold_rules(
    rules: RuleTable, state: DeviceState, batch: EventBatch,
    accepted: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dense [B, R] rule evaluation over measurement events.

    Each rule compares the quantity its ``kind`` selects — the current
    sample, a trailing EWMA (per-rule time scale), or the rate of change
    since the device's previous sample — against its threshold, in ONE
    fused pass (reference SPI is per-event callbacks,
    ``spi/IRuleProcessor.java:50-97``; windowed logic there would be
    host-side processor state).

    Returns ``(fired_any, first_rule_id, ewma_candidates)`` — the
    candidates feed :func:`update_device_state` so the trailing stats
    are folded exactly once.
    """
    is_meas = accepted & (batch.event_type == EventType.MEASUREMENT)
    v = batch.value

    prev_ts, prev_ns, prev_v, ewma_prev = _gather_meas_state(state, batch)
    seeded = prev_ts > 0
    # sub-second resolution (rate rules must fire for > 1 Hz sensors)
    dt = jnp.maximum(
        (batch.ts_s - prev_ts).astype(jnp.float32)
        + (batch.ts_ns - prev_ns).astype(jnp.float32) * 1e-9, 0.0)
    rate_valid = seeded & (dt > 0)
    rate = jnp.where(rate_valid, (v - prev_v) / jnp.maximum(dt, 1e-9), 0.0)

    ewma_new = _fold_ewma_from(
        prev_ts, prev_ns, ewma_prev, batch, rules.ewma_tau_s)  # [B, K]
    widx = jnp.clip(rules.window_idx, 0, rules.num_ewma_scales - 1)
    # One-hot matmul instead of jnp.take along axis 1: the [B, R] gather
    # lowers to a slow scalar path; the [B, K] @ [K, R] product rides the
    # MXU.  HIGHEST precision keeps the selection exact (default TPU
    # matmul precision would round the EWMAs to bfloat16, letting
    # borderline WINDOW_MEAN rules flap against the exact EWMA stored in
    # device state).
    onehot = (widx[None, :] == jnp.arange(rules.num_ewma_scales)[:, None]
              ).astype(ewma_new.dtype)  # [K, R]
    e_sel = jnp.matmul(ewma_new, onehot,
                       precision=jax.lax.Precision.HIGHEST)  # [B, R]

    kind = rules.kind[None, :]
    val = jnp.where(
        kind == RuleKind.INSTANT, v[:, None],
        jnp.where(kind == RuleKind.WINDOW_MEAN, e_sel, rate[:, None]),
    )
    # a rate rule needs a previous sample with a positive gap
    kind_ok = jnp.where(kind == RuleKind.RATE_PER_S,
                        rate_valid[:, None], True)

    thr = rules.threshold[None, :]  # [1, R]
    op = rules.op[None, :]
    hit = compare_select(op, val, thr)  # [B, R]

    tenant_ok = (rules.tenant_id[None, :] == NULL_ID) | (
        rules.tenant_id[None, :] == batch.tenant_id[:, None]
    )
    mtype_ok = (rules.mtype_id[None, :] == NULL_ID) | (
        rules.mtype_id[None, :] == batch.mtype_id[:, None]
    )
    fired = (hit & kind_ok & tenant_ok & mtype_ok
             & rules.active[None, :] & is_meas[:, None])
    fired_any = fired.any(axis=1)
    first = jnp.argmax(fired, axis=1).astype(jnp.int32)
    return fired_any, jnp.where(fired_any, first, NULL_ID), ewma_new


def eval_zone_rules(
    zones: ZoneTable, batch: EventBatch, accepted: jax.Array, area_id: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Geofence evaluation over location events.

    Reference: ``ZoneTestRuleProcessor.onLocation`` tests each location
    against cached zone polygons and fires a configured alert.  Zone
    applicability = active ∧ tenant match ∧ (zone area wildcard or equal to
    the event's enriched area).
    """
    is_loc = accepted & (batch.event_type == EventType.LOCATION)
    pts = jnp.stack([batch.lon, batch.lat], axis=-1)  # (x, y)
    inside = points_in_polygons_auto(pts, zones.verts)  # [B, Z] (Pallas when large)

    tenant_ok = (zones.tenant_id[None, :] == NULL_ID) | (
        zones.tenant_id[None, :] == batch.tenant_id[:, None]
    )
    area_ok = (zones.area_id[None, :] == NULL_ID) | (
        zones.area_id[None, :] == area_id[:, None]
    )
    applies = zones.active[None, :] & tenant_ok & area_ok & is_loc[:, None]
    cond_inside = zones.condition[None, :] == ZoneCondition.ALERT_IF_INSIDE
    fired = applies & jnp.where(cond_inside, inside, ~inside)
    fired_any = fired.any(axis=1)
    first = jnp.argmax(fired, axis=1).astype(jnp.int32)
    return fired_any, jnp.where(fired_any, first, NULL_ID)


def update_device_state(
    state: DeviceState, batch: EventBatch, accepted: jax.Array,
    ewma_candidates: Optional[jax.Array] = None,
) -> Tuple[DeviceState, jax.Array]:
    """Merge accepted events into last-known state (time-ordered scatters).

    Reference: ``DeviceStateProcessingLogic.java:46-80`` merges each event
    into the per-device state doc; here each event-type family updates its
    columns via :func:`scatter_last_by_time`.  Rows with
    ``update_state=False`` (system-generated events, reference
    ``IDeviceEvent.isUpdateState()``) are persisted/fanned-out upstream but
    never merged here — and never mark a device present.

    Returns ``(new_state, present_now)`` where ``present_now`` is
    ``bool[capacity]`` — devices this step merged at least one event into
    (the presence signal, free from the any-event winner map).
    """
    ids = batch.device_id
    accepted = accepted & batch.update_state

    # One sort-based winner map per state family (sorts measured ~0.1 ms
    # each at width 131k on v5e; a batched segmented associative scan
    # sharing one sort was tried and measured 11 ms — log-depth scans do
    # 17 unfused HBM passes, sorts are native).  The any-event map doubles
    # as the presence signal, so presence costs no extra scatter.
    M = state.num_mtype_slots
    is_loc = accepted & (batch.event_type == EventType.LOCATION)
    is_alert = accepted & (batch.event_type == EventType.ALERT)
    # Measurement matrix: slot = mtype_id mod M (host keeps mtype handles
    # dense per tenant; collisions degrade to "newest of colliding types",
    # documented in schema.DeviceState).  Unknown measurement types
    # (mtype_id == NULL_ID) are dropped, not aliased onto slot 0.
    is_meas = accepted & (batch.event_type == EventType.MEASUREMENT) & (
        batch.mtype_id >= 0
    )
    flat_ids = ids * M + batch.mtype_id % M
    any_rows = winner_rows(ids, batch.ts_s, batch.ts_ns, accepted, state.capacity)
    loc_rows = winner_rows(ids, batch.ts_s, batch.ts_ns, is_loc, state.capacity)
    alert_rows = winner_rows(ids, batch.ts_s, batch.ts_ns, is_alert, state.capacity)
    meas_rows = winner_rows(
        flat_ids, batch.ts_s, batch.ts_ns, is_meas, state.capacity * M)

    # Any-event columns.
    new_s, new_ns, (new_type,) = apply_winners(
        any_rows,
        state.last_event_ts_s,
        state.last_event_ts_ns,
        (state.last_event_type,),
        batch.ts_s,
        batch.ts_ns,
        (batch.event_type,),
    )
    # An accepted event marks the device present again (reference:
    # DevicePresenceManager resets on new events).
    presence = state.presence_missing & ~(any_rows >= 0)

    # Location columns.
    loc_s, loc_ns, (lat, lon, elev) = apply_winners(
        loc_rows,
        state.last_location_ts_s,
        state.last_location_ts_ns,
        (state.last_lat, state.last_lon, state.last_elevation),
        batch.ts_s,
        batch.ts_ns,
        (batch.lat, batch.lon, batch.elevation),
    )

    # Alert columns.
    alert_s, alert_ns, (alert_code,) = apply_winners(
        alert_rows,
        state.last_alert_ts_s,
        state.last_alert_ts_ns,
        (state.last_alert_code,),
        batch.ts_s,
        batch.ts_ns,
        (batch.alert_code,),
    )

    # EWMA candidates fold each row's sample against PRE-batch state; the
    # scatter's newest-wins pick applies them consistently with values.
    # (Multiple same-slot events in one batch collapse to the newest —
    # sub-deadline granularity, documented EWMA approximation.)  Callers
    # outside pipeline_step (direct state updates in tests/tools) get the
    # default time-scales; pass the RuleTable's taus to stay in sync with
    # rule evaluation.
    if ewma_candidates is None:
        base = list(DEFAULT_EWMA_TAUS)
        k = state.num_ewma_scales
        taus = jnp.asarray((base + [base[-1]] * k)[:k], jnp.float32)
        ewma_candidates = fold_ewma(state, batch, taus)
    val_s, val_ns, (values, ewma) = apply_winners(
        meas_rows,
        state.last_value_ts_s.reshape(-1),
        state.last_value_ts_ns.reshape(-1),
        (state.last_values.reshape(-1),
         state.ewma_values.reshape(-1, state.num_ewma_scales)),
        batch.ts_s,
        batch.ts_ns,
        (batch.value, ewma_candidates),
    )

    mshape = state.last_value_ts_s.shape
    new_state = state.replace(
        last_event_ts_s=new_s,
        last_event_ts_ns=new_ns,
        last_event_type=new_type,
        presence_missing=presence,
        last_location_ts_s=loc_s,
        last_location_ts_ns=loc_ns,
        last_lat=lat,
        last_lon=lon,
        last_elevation=elev,
        last_alert_ts_s=alert_s,
        last_alert_ts_ns=alert_ns,
        last_alert_code=alert_code,
        last_value_ts_s=val_s.reshape(mshape),
        last_value_ts_ns=val_ns.reshape(mshape),
        last_values=values.reshape(state.last_values.shape),
        ewma_values=ewma.reshape(state.ewma_values.shape),
    )
    return new_state, any_rows >= 0


def _build_derived_alerts(
    batch: EventBatch,
    rules: RuleTable,
    zones: ZoneTable,
    rule_id: jax.Array,
    zone_id: jax.Array,
) -> EventBatch:
    """Alert events fired by rules, ready for re-injection.

    Reference: rule processors create alert events back through event
    management (``ZoneTestRuleProcessor.java:60``).  Zone alerts take
    priority over threshold alerts when both fire for one source event.
    """
    rule_fired = rule_id != NULL_ID
    zone_fired = zone_id != NULL_ID
    fired = rule_fired | zone_fired

    safe_rule = jnp.clip(rule_id, 0, rules.capacity - 1)
    safe_zone = jnp.clip(zone_id, 0, zones.capacity - 1)
    # Packed [B, 2] gathers (code, level) per table — halves the [B]-sized
    # gather count (each ~1 ms at width 131k on v5e).
    rpack = jnp.stack([rules.alert_code, rules.alert_level], axis=1)[safe_rule]
    zpack = jnp.stack([zones.alert_code, zones.alert_level], axis=1)[safe_zone]
    code = jnp.where(zone_fired, zpack[:, 0], rpack[:, 0])
    level = jnp.where(zone_fired, zpack[:, 1], rpack[:, 1])
    empty = EventBatch.empty(batch.width)
    return empty.replace(
        valid=fired,
        device_id=jnp.where(fired, batch.device_id, NULL_ID),
        tenant_id=jnp.where(fired, batch.tenant_id, NULL_ID),
        event_type=jnp.full_like(batch.event_type, EventType.ALERT),
        ts_s=batch.ts_s,
        ts_ns=batch.ts_ns,
        alert_code=jnp.where(fired, code, NULL_ID),
        alert_level=jnp.where(fired, level, 0),
        # Derived events carry the source event's journal ref so the host
        # can link alert → cause (reference: alert events reference the
        # triggering event ids).
        payload_ref=batch.payload_ref,
        # System-generated: persist + fan out, but never merge into
        # last-known state or mark the device present.
        update_state=jnp.zeros_like(fired),
    )


#: ``jax.named_scope`` names of the stages :func:`pipeline_step` composes
STEP_STAGES = ("validate_enrich", "threshold_rules", "zone_rules",
               "state_update", "derived_alerts")


def pipeline_step(
    registry: Registry,
    state: DeviceState,
    rules: RuleTable,
    zones: ZoneTable,
    batch: EventBatch,
) -> Tuple[DeviceState, PipelineOutputs]:
    """The fused inbound step: validate → enrich → rules → state → outputs.

    Pure function of its inputs — jit/pjit it once and feed batches forever.
    The five stages carry ``jax.named_scope`` names (:data:`STEP_STAGES`)
    — metadata only: a profiler capture shows them as each op's
    ``op_name`` prefix, the compiled program is the same.
    """
    with jax.named_scope("validate_enrich"):
        accepted, unregistered, unassigned, enrich = validate_and_enrich(
            registry, batch)
    # Numeric integrity: a NaN/Inf in any float column would flow through
    # the EWMA fold, the rule compares (NE is True for NaN!) and the
    # time-ordered scatters straight into CARRIED state — poisoning the
    # device's history forever.  Clean rows feed rules/state; raw
    # ``accepted`` still routes persistence so nothing is silently lost.
    finite = (jnp.isfinite(batch.value) & jnp.isfinite(batch.lat)
              & jnp.isfinite(batch.lon) & jnp.isfinite(batch.elevation))
    nonfinite = batch.valid & ~finite
    clean = accepted & finite
    with jax.named_scope("threshold_rules"):
        rule_fired, rule_id, ewma_candidates = eval_threshold_rules(
            rules, state, batch, clean)
    with jax.named_scope("zone_rules"):
        zone_fired, zone_id = eval_zone_rules(
            zones, batch, clean, enrich["area_id"])
    with jax.named_scope("state_update"):
        new_state, present_now = update_device_state(
            state, batch, clean, ewma_candidates)
        # Per-device attribution rides device state (one scatter-add, no
        # host sync): the quarantine threshold is evaluated host-side
        # from the packed telemetry scalar + this counter.
        cap = state.capacity
        nf_idx = jnp.where(nonfinite & (batch.device_id >= 0)
                           & (batch.device_id < cap), batch.device_id, cap)
        new_state = new_state.replace(
            nonfinite_count=new_state.nonfinite_count.at[nf_idx].add(
                1, mode="drop"))
    with jax.named_scope("derived_alerts"):
        derived = _build_derived_alerts(
            batch, rules, zones, rule_id, zone_id)

    metrics = StepMetrics(
        processed=batch.valid.sum().astype(jnp.int32),
        accepted=accepted.sum().astype(jnp.int32),
        unregistered=unregistered.sum().astype(jnp.int32),
        unassigned=unassigned.sum().astype(jnp.int32),
        threshold_alerts=rule_fired.sum().astype(jnp.int32),
        zone_alerts=zone_fired.sum().astype(jnp.int32),
        by_type=bincount_fixed(batch.event_type, accepted, NUM_EVENT_TYPES),
    )
    outputs = PipelineOutputs(
        accepted=accepted,
        unregistered=unregistered,
        unassigned=unassigned,
        nonfinite=nonfinite,
        rule_id=rule_id,
        zone_id=zone_id,
        present_now=present_now,
        derived_alerts=derived,
        metrics=metrics,
        **enrich,
    )
    return new_state, outputs

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
    bincount_fixed,
    drop_targets,
    newer_or_equal,
    set_rows,
    winning_rows,
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


#: Registry columns in the order :func:`validate_rows` reads them (and
#: ``pipeline/packed.py`` packs them)
REGISTRY_FIELDS = ("active", "tenant_id", "assignment_status",
                   "device_type_id", "assignment_id", "area_id",
                   "customer_id", "asset_id")


class RegistryColumns:
    """The unpacked :class:`Registry` (one array per column) as the fused
    step sees a registry: ``rows_at`` gathers the batch's B rows.
    ``PackedTables`` offers the same over its packed table."""

    def __init__(self, registry: Registry):
        self.registry = registry
        self.capacity = registry.capacity

    def rows_at(self, ids_safe: jax.Array) -> jax.Array:
        """``int32[B, 8]`` (:data:`REGISTRY_FIELDS`): one B-row gather
        per column — nothing registry-sized is stacked first."""
        return jnp.stack(
            [getattr(self.registry, f)[ids_safe].astype(jnp.int32)
             for f in REGISTRY_FIELDS], axis=1)


def validate_and_enrich(
    registry: Registry, batch: EventBatch
) -> Tuple[jax.Array, jax.Array, jax.Array, dict]:
    """:func:`validate_rows` for an unpacked :class:`Registry`."""
    return validate_rows(RegistryColumns(registry), batch)


def validate_rows(
    registry, batch: EventBatch
) -> Tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Registry gather replacing the per-event device/assignment lookups.

    Reference: ``InboundPayloadProcessingLogic.validateAssignment:185-219``
    — device-by-token then assignment lookup over cached gRPC; missing
    device → unregistered dead-letter (``:228-233``), missing/inactive
    assignment → unassigned dead-letter.  ``registry`` is anything with a
    ``capacity`` and ``rows_at`` (:class:`RegistryColumns`,
    ``PackedTables``).
    """
    cap = registry.capacity
    ids = batch.device_id
    in_range = (ids >= 0) & (ids < cap)
    packed = registry.rows_at(jnp.clip(ids, 0, cap - 1))  # [B, 8]

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


@struct.dataclass
class StateRows:
    """The pre-batch state of the slots a batch names: one entry per
    BATCH row, gathered once a step at the row's device (and, for the
    measurement columns, the row's measurement slot).  Rule evaluation
    and the state update both read it; nothing registry-sized is built
    to produce it."""

    ev_s: jax.Array      # int32[B] — last_event_ts_s of the row's device
    ev_ns: jax.Array     # int32[B]
    loc_s: jax.Array     # int32[B] — last_location_ts_s
    loc_ns: jax.Array    # int32[B]
    alert_s: jax.Array   # int32[B] — last_alert_ts_s
    alert_ns: jax.Array  # int32[B]
    val_s: jax.Array     # int32[B] — last_value_ts_s[device, slot]
    val_ns: jax.Array    # int32[B]
    value: jax.Array     # float32[B] — last_values[device, slot]
    ewma: jax.Array      # float32[B, K] — ewma_values[device, slot]
    # the gathered rows as the carry holds them, for a carry whose
    # scatter writes whole rows back (PackedState); None otherwise
    raw: Optional[jax.Array] = None


@struct.dataclass
class RowWrites:
    """Which batch rows write which state family (``bool[B]`` each): the
    row won its slot among the batch's rows AND is at least as new as
    what the slot holds.  ``present`` is the any-event winner whatever
    its age — it clears ``presence_missing`` and marks ``present_now``."""

    present: jax.Array
    event: jax.Array
    location: jax.Array
    alert: jax.Array
    measurement: jax.Array


def slot_address(batch: EventBatch, capacity: int, num_mtype_slots: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """``(device row clipped into range, measurement slot)`` per batch
    row — where :class:`StateRows` is gathered.  Slot = ``mtype_id mod
    M``; rows without a measurement type read slot 0 and never write."""
    ids_safe = jnp.clip(batch.device_id, 0, capacity - 1)
    slot = jnp.where(batch.mtype_id >= 0, batch.mtype_id % num_mtype_slots, 0)
    return ids_safe, slot


def _gather_meas_state(
    state: DeviceState, batch: EventBatch
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-row previous measurement-slot state, read straight from the
    ``[D, M]`` / ``[D, M, K]`` columns at ``(device, slot)``: B rows are
    gathered from each, nothing is stacked or reshaped first.

    Returns ``(prev_ts, prev_ns, prev_value, ewma_prev[B, K])``.
    """
    ids_safe, slot = slot_address(
        batch, state.capacity, state.num_mtype_slots)
    return (state.last_value_ts_s[ids_safe, slot],
            state.last_value_ts_ns[ids_safe, slot],
            state.last_values[ids_safe, slot],
            state.ewma_values[ids_safe, slot])


class StateColumns:
    """The unpacked carry (:class:`DeviceState`, one array per column) as
    the fused step sees a carry: ``gather`` the batch's rows, ``scatter``
    the winners back.  :class:`~sitewhere_tpu.pipeline.packed.PackedState`
    offers the same two methods over its one packed buffer."""

    def __init__(self, state: DeviceState):
        self.state = state
        self.capacity = state.capacity
        self.num_mtype_slots = state.num_mtype_slots
        self.num_ewma_scales = state.num_ewma_scales

    def gather(self, batch: EventBatch) -> StateRows:
        s = self.state
        ids_safe, _ = slot_address(batch, s.capacity, s.num_mtype_slots)
        val_s, val_ns, value, ewma = _gather_meas_state(s, batch)
        return StateRows(
            ev_s=s.last_event_ts_s[ids_safe],
            ev_ns=s.last_event_ts_ns[ids_safe],
            loc_s=s.last_location_ts_s[ids_safe],
            loc_ns=s.last_location_ts_ns[ids_safe],
            alert_s=s.last_alert_ts_s[ids_safe],
            alert_ns=s.last_alert_ts_ns[ids_safe],
            val_s=val_s, val_ns=val_ns, value=value, ewma=ewma)

    def scatter(self, batch: EventBatch, cur: StateRows, writes: RowWrites,
                ewma: jax.Array, nonfinite: Optional[jax.Array] = None,
                ) -> DeviceState:
        """Write the winning rows into their columns (one unique-index
        scatter of B rows per column) and count ``nonfinite`` rows per
        device; returns the new state."""
        s = self.state
        cap = s.capacity
        ids = batch.device_id
        _, slot = slot_address(batch, cap, s.num_mtype_slots)
        put = set_rows
        t_present = drop_targets(ids, writes.present, cap)
        t_event = drop_targets(ids, writes.event, cap)
        t_loc = drop_targets(ids, writes.location, cap)
        t_alert = drop_targets(ids, writes.alert, cap)
        t_meas = (drop_targets(ids, writes.measurement, cap), slot)
        nonfinite_count = s.nonfinite_count
        if nonfinite is not None:
            nf = jnp.where(nonfinite & (ids >= 0) & (ids < cap), ids, cap)
            nonfinite_count = nonfinite_count.at[nf].add(1, mode="drop")
        return s.replace(
            last_event_ts_s=put(s.last_event_ts_s, t_event, batch.ts_s),
            last_event_ts_ns=put(s.last_event_ts_ns, t_event, batch.ts_ns),
            last_event_type=put(s.last_event_type, t_event, batch.event_type),
            presence_missing=put(s.presence_missing, t_present, False),
            last_location_ts_s=put(s.last_location_ts_s, t_loc, batch.ts_s),
            last_location_ts_ns=put(s.last_location_ts_ns, t_loc, batch.ts_ns),
            last_lat=put(s.last_lat, t_loc, batch.lat),
            last_lon=put(s.last_lon, t_loc, batch.lon),
            last_elevation=put(s.last_elevation, t_loc, batch.elevation),
            last_alert_ts_s=put(s.last_alert_ts_s, t_alert, batch.ts_s),
            last_alert_ts_ns=put(s.last_alert_ts_ns, t_alert, batch.ts_ns),
            last_alert_code=put(s.last_alert_code, t_alert, batch.alert_code),
            last_value_ts_s=put(s.last_value_ts_s, t_meas, batch.ts_s),
            last_value_ts_ns=put(s.last_value_ts_ns, t_meas, batch.ts_ns),
            last_values=put(s.last_values, t_meas, batch.value),
            ewma_values=put(s.ewma_values, t_meas, ewma),
            nonfinite_count=nonfinite_count,
        )


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
    """Per-row candidate EWMAs after folding this row's sample into its
    pre-gathered slot state.

    Irregular-sampling EWMA: ``alpha = 1 - exp(-dt / tau)`` with ``dt``
    the gap since the device's previous sample in that measurement slot;
    the first sample seeds the average (no zero bias).  Returns
    ``float32[B, K]`` — rows are CANDIDATES; the state update picks each
    slot's winner.
    """
    return fold_ewma_arrays(prev_ts, prev_ns, ewma_prev,
                            batch.ts_s, batch.ts_ns, batch.value, taus)


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
    """:func:`threshold_rules_on` for an unpacked :class:`DeviceState`:
    gathers the batch's measurement-slot rows from it first."""
    return threshold_rules_on(
        rules, _gather_meas_state(state, batch), batch, accepted)


def threshold_rules_on(
    rules: RuleTable,
    prev: Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
    batch: EventBatch, accepted: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dense [B, R] rule evaluation over measurement events.

    Each rule compares the quantity its ``kind`` selects — the current
    sample, a trailing EWMA (per-rule time scale), or the rate of change
    since the device's previous sample — against its threshold, in ONE
    fused pass (reference SPI is per-event callbacks,
    ``spi/IRuleProcessor.java:50-97``; windowed logic there would be
    host-side processor state).  ``prev`` is the batch's pre-batch
    measurement-slot state ``(ts_s, ts_ns, value, ewma[B, K])``, as the
    carry's ``gather`` returns it.

    Returns ``(fired_any, first_rule_id, ewma_candidates)`` — the
    candidates feed :func:`update_device_state` so the trailing stats
    are folded exactly once.
    """
    is_meas = accepted & (batch.event_type == EventType.MEASUREMENT)
    v = batch.value

    prev_ts, prev_ns, prev_v, ewma_prev = prev
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


def default_ewma_taus(num_ewma_scales: int) -> jax.Array:
    """The default EWMA time-scales, the last repeated up to ``K``."""
    base = list(DEFAULT_EWMA_TAUS)
    return jnp.asarray(
        (base + [base[-1]] * num_ewma_scales)[:num_ewma_scales], jnp.float32)


def plan_state_writes(
    cur: StateRows, batch: EventBatch, accepted: jax.Array,
    capacity: int, num_mtype_slots: int,
) -> RowWrites:
    """Elect the batch rows that write each state family — the whole
    state merge short of the scatter, on B rows.

    Rows with ``update_state=False`` (system-generated events, reference
    ``IDeviceEvent.isUpdateState()``) are persisted/fanned-out upstream
    but never merged — and never mark a device present.

    TWO sorts elect every winner (:func:`winning_rows`): one over the
    device id for the any-event columns, and one for the three typed
    families together — a row is a location, an alert or a measurement,
    never two, so their slots share one id space (``[0, D*M)`` the
    measurement matrix, then D location slots, then D alert slots) and
    one sort ranks each family exactly as a sort of its own would.
    Measurement slot = ``mtype_id mod M`` (host keeps mtype handles
    dense per tenant; collisions degrade to "newest of colliding types",
    documented in schema.DeviceState); unknown measurement types
    (``mtype_id == NULL_ID``) are dropped, not aliased onto slot 0.
    """
    D, M = capacity, num_mtype_slots
    if D * (M + 2) + 1 >= 2 ** 31:
        raise ValueError(
            f"capacity {D} x ({M} + 2) slot ids overflow int32")
    ids = batch.device_id
    ok = accepted & batch.update_state & (ids >= 0) & (ids < D)
    is_loc = ok & (batch.event_type == EventType.LOCATION)
    is_alert = ok & (batch.event_type == EventType.ALERT)
    is_meas = ok & (batch.event_type == EventType.MEASUREMENT) & (
        batch.mtype_id >= 0)
    typed_id = jnp.where(
        is_meas, ids * M + batch.mtype_id % M,
        jnp.where(is_loc, D * M + ids, D * (M + 1) + ids))
    key = (batch.ts_s, batch.ts_ns)
    present = winning_rows(ids, key, ok, D)
    typed_win = winning_rows(
        typed_id, key, is_loc | is_alert | is_meas, D * (M + 2))
    cur_s = jnp.where(is_meas, cur.val_s,
                      jnp.where(is_loc, cur.loc_s, cur.alert_s))
    cur_ns = jnp.where(is_meas, cur.val_ns,
                       jnp.where(is_loc, cur.loc_ns, cur.alert_ns))
    typed = typed_win & newer_or_equal(batch.ts_s, batch.ts_ns, cur_s, cur_ns)
    return RowWrites(
        present=present,
        event=present & newer_or_equal(
            batch.ts_s, batch.ts_ns, cur.ev_s, cur.ev_ns),
        location=typed & is_loc,
        alert=typed & is_alert,
        measurement=typed & is_meas,
    )


def merge_into_carry(
    carry, cur: StateRows, batch: EventBatch, accepted: jax.Array,
    ewma_candidates: Optional[jax.Array] = None,
    nonfinite: Optional[jax.Array] = None,
):
    """Merge accepted events into ``carry`` (:class:`StateColumns` or a
    ``PackedState``) given the rows ``cur`` gathered from it: elect the
    writers (:func:`plan_state_writes`), scatter them.  Returns
    ``(new_state, present_now)`` in the carry's own form.

    EWMA candidates fold each row's sample against PRE-batch state; the
    newest-wins pick applies them consistently with values.  (Multiple
    same-slot events in one batch collapse to the newest — sub-deadline
    granularity, documented EWMA approximation.)  Callers outside
    pipeline_step (direct state updates in tests/tools) get the default
    time-scales; pass the RuleTable's taus to stay in sync with rule
    evaluation.
    """
    if ewma_candidates is None:
        ewma_candidates = _fold_ewma_from(
            cur.val_s, cur.val_ns, cur.ewma, batch,
            default_ewma_taus(carry.num_ewma_scales))
    cap = carry.capacity
    writes = plan_state_writes(
        cur, batch, accepted, cap, carry.num_mtype_slots)
    # the one registry-sized write of the step: a zero fill and B rows
    present_now = set_rows(
        jnp.zeros((cap,), bool),
        drop_targets(batch.device_id, writes.present, cap), True)
    return (carry.scatter(batch, cur, writes, ewma_candidates, nonfinite),
            present_now)


def update_device_state(
    state: DeviceState, batch: EventBatch, accepted: jax.Array,
    ewma_candidates: Optional[jax.Array] = None,
) -> Tuple[DeviceState, jax.Array]:
    """Merge accepted events into last-known state.

    Reference: ``DeviceStateProcessingLogic.java:46-80`` merges each event
    into the per-device state doc.  Here the update reads and writes only
    the rows the batch names: the slots' current time keys are gathered
    at the batch's ids, the writers are elected among the B rows
    (:func:`plan_state_writes`), and each column takes one unique-index
    scatter of B rows.  Nothing is sized by the registry but
    ``present_now``.

    Returns ``(new_state, present_now)`` where ``present_now`` is
    ``bool[capacity]`` — devices this step merged at least one event into
    (the presence signal: a zero fill and one B-row scatter).
    """
    carry = StateColumns(state)
    return merge_into_carry(
        carry, carry.gather(batch), batch, accepted, ewma_candidates)


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
    # Packed [B, 2] gathers (code, level) per table: one gather of B
    # two-column rows instead of two of B scalars.
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
    :func:`fused_step` over the unpacked carry.  Not served: the
    dispatcher steps packed plans only (pipeline/packed.py).  This is the
    reference the packed programs are compared against in tests and the
    carrier of the calibration probe (pipeline/telemetry.py).
    """
    return fused_step(RegistryColumns(registry), StateColumns(state),
                      rules, zones, batch)


def fused_step(registry, carry, rules: RuleTable,
               zones: ZoneTable, batch: EventBatch):
    """The step over any registry that can look up the batch's rows
    (:class:`RegistryColumns`, ``PackedTables``) and any carry that can
    ``gather`` them and ``scatter`` the winners back
    (:class:`StateColumns`, ``PackedState``): registry and state are
    touched exactly there, and every stage between works on batch-sized
    arrays.  Returns ``(new_state,
    outputs)``, the state in the carry's own form.

    The five stages carry ``jax.named_scope`` names (:data:`STEP_STAGES`)
    — metadata only: a profiler capture shows them as each op's
    ``op_name`` prefix, the compiled program is the same.
    """
    with jax.named_scope("validate_enrich"):
        accepted, unregistered, unassigned, enrich = validate_rows(
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
        cur = carry.gather(batch)
        rule_fired, rule_id, ewma_candidates = threshold_rules_on(
            rules, (cur.val_s, cur.val_ns, cur.value, cur.ewma), batch, clean)
    with jax.named_scope("zone_rules"):
        zone_fired, zone_id = eval_zone_rules(
            zones, batch, clean, enrich["area_id"])
    with jax.named_scope("state_update"):
        # Per-device nonfinite attribution rides device state (one
        # scatter-add, no host sync): the quarantine threshold is
        # evaluated host-side from the packed telemetry scalar + this
        # counter.
        new_state, present_now = merge_into_carry(
            carry, cur, batch, clean, ewma_candidates, nonfinite)
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

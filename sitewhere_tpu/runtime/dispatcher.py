"""Pipeline dispatcher: the host loop driving the fused TPU step.

This is the TPU reshape of the reference's inbound-processing service
(``InboundPayloadProcessingLogic.java:135-159`` — Kafka poll → per-record
thread-pool tasks → per-event gRPC) plus the enrichment forwarding
(``OutboundPayloadEnrichmentLogic.java:54-88``) and the fan-out consumers:
instead of processes connected by Kafka topics, ONE host thread cycles

    batcher → jitted pipeline step (device) → routed host egress

where egress covers everything the reference spreads over five services:

- accepted rows  → event store append (event-management persistence)
- enriched cols  → outbound connector workers (outbound-connectors) —
  which also host rule-processor callbacks (rule-processing)
- command rows   → command processor (command-delivery)
- unregistered   → registration manager → replay (device-registration,
  reprocess topic)
- derived alerts + presence state-changes → re-injected into the batcher
- new state      → DeviceStateManager.commit (device-state), sweep-safe

Overlapped host pipeline: the host half of the event path is split into
stages that overlap the device step instead of serializing behind it —
the only work left on the critical dispatch thread is batch assembly +
jitted-step launch:

- DECODE runs on the ingest decode pool (``ingest/sources.py``
  DecodePool → :meth:`PipelineDispatcher.decode_wire_lines`): window
  N+1's ``decode_json_lines`` runs while window N is on device, with
  per-source sequence keys keeping delivery (journal + batch) in
  submission order.
- H2D is double-buffered: plans stage their packed buffers via
  ``device_put`` at emission (``pipeline/packed.py stage_packed_batch``;
  the CPU backend transfers synchronously inside the jitted call), so
  the next plan's transfer overlaps the current step.
- EGRESS (persistence, outbound fan-out, command delivery, replay) runs
  on a supervised offload worker pulling from the bounded in-flight
  window; the dispatch thread stalls only when egress falls a full
  window behind (backpressure).  The at-least-once rule is unchanged:
  the journal offset only advances past plans whose egress COMPLETED —
  a crashed egress leaves its plan outstanding and the commit gate
  fails closed.
- The STEP itself is device-resident at depth (the promoted phase-C
  packed chain): full-width fill plans collect in a K-slot ring of
  pre-staged H2D inputs, and ONE jitted ``lax.fori_loop`` chain steps
  all K with the ``PackedState`` carry threading on device — the host
  dispatches once and, via the ring's shared output fetch, syncs once
  per K steps instead of per step (``pipeline.host_syncs`` counts it).
  Commits stay per batch: each slot windows as its own plan, so a
  mid-ring egress crash leaves exactly the uncommitted steps
  outstanding.  Deadline/flush partials, re-injected plans, mesh and
  CPU-default deployments all take the single-step path (draining
  ring-held predecessors in order first), so the ring only engages
  where it pays: sustained full-width traffic on a host-attached chip.

Output fetches stay selective: batch columns never round-trip (the
batcher keeps its numpy originals in ``BatchPlan``), device→host copies
start asynchronously at dispatch, and the unregistered mask /
derived-alert rows are fetched only when the step's metric counters say
they exist.

A plan's life on the host is a chain of timers, each also a profiler
span of the same name (``runtime/metrics.py Timer.time``; spans of one
plan share ``seq``): ``pipeline.stage_decode_s`` and
``ingest.journal_append_s`` per payload; per plan ``stage_batch_s`` →
``stage_place_s`` (on a mesh: the plan's per-shard placement) →
``stage_dispatch_wait_s`` (full egress window + step-lock wait) →
``stage_dispatch_s`` → ``stage_inflight_wait_s`` (queued for egress)
→ ``stage_egress_s``, of which ``pipeline.device_wait_s`` is the part
blocked on the device and D2H; ring plans have ``stage_ring_wait_s`` /
``stage_ring_dispatch_s`` in place of the dispatch pair.  With the batcher wait
(``plan.max_wait_s``) they add up to the plan's latency; when the
stage totals exceed wall elapsed, the stages are provably overlapping.
"""

from __future__ import annotations

import collections
import collections.abc
import functools
import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from sitewhere_tpu.analysis.markers import hot_path
from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.ingest.batcher import Batcher, BatchPlan
from sitewhere_tpu.ingest.decoders import DecodedRequest
from sitewhere_tpu.ingest.journal import Journal, JournalReader
from sitewhere_tpu.runtime import faults
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent
from sitewhere_tpu.runtime.resilience import dead_letter
from sitewhere_tpu.schema import EventType, as_numpy
from sitewhere_tpu.store import segment as _segment_schema

logger = logging.getLogger("sitewhere_tpu.dispatcher")

# egress-view split of the canonical storage schema: the 5 step-output
# enrichment columns, and everything else (minus the store-stamped
# receive time) resolving straight out of plan.host_cols.  Derived, not
# hand-maintained — a copy would silently desync from store COLUMNS.
_EGRESS_ENRICHMENT = ("device_type_id", "assignment_id", "area_id",
                      "customer_id", "asset_id")
_EGRESS_HOST = tuple(
    n for n in _segment_schema.COLUMN_NAMES
    if n not in _EGRESS_ENRICHMENT and n != "received_s"
)


class _StepFailed(Exception):
    """The launch/commit of a packed single step raised — the one
    containable region of ``_dispatch_plan`` (the cause rides
    ``__cause__``; ``_contain_step_failure`` bisects the batch)."""


class EgressColumns(collections.abc.Mapping):
    """Zero-copy egress column view over one plan's host columns plus
    the step's enrichment outputs.

    Replaces the per-batch dict build in ``_columns`` and its 5 EAGER
    ``np.asarray`` enrichment fetches.  Host columns resolve straight
    out of ``plan.host_cols``; enrichment columns (``device_type_id`` …
    ``asset_id``) fetch from the step output LAZILY on first access and
    memoize, so an egress
    where no consumer touches them — store disabled, outbound-only
    fan-out — never pays the device sync at all, and the common path
    pays it exactly once per column (the segment store's
    ``append_columns`` touches all five, caching them for the async
    outbound/analytics consumers that run afterwards)."""

    ENRICHMENT_COLUMNS = _EGRESS_ENRICHMENT
    _ENRICH_SET = frozenset(_EGRESS_ENRICHMENT)
    HOST_COLUMNS = _EGRESS_HOST
    # O(1) membership: connectors look fields up per row per batch
    _HOST_SET = frozenset(_EGRESS_HOST)

    __slots__ = ("_host", "_out", "_fetched", "_fetch_lock")

    def __init__(self, host_cols: Dict[str, np.ndarray], out):
        self._host = host_cols
        self._out = out
        self._fetched: Optional[Dict[str, np.ndarray]] = None
        # one view is shared across the egress thread AND every async
        # outbound/analytics consumer; the enrichment fetch must be
        # thread-safe (the lock is per batch, taken at most once per
        # consumer — the fast path below is a lock-free memo read)
        self._fetch_lock = threading.Lock()

    def _enrichment(self) -> Dict[str, np.ndarray]:
        fetched = self._fetched
        if fetched is None:
            with self._fetch_lock:
                fetched = self._fetched
                if fetched is None:
                    out = self._out
                    # all five at once (matching the old eager cost the
                    # first time ANY consumer asks), then release the
                    # step output so a view parked in a lagging
                    # outbound queue doesn't pin the step's device
                    # buffers
                    fetched = {
                        n: np.asarray(getattr(out, n))
                        for n in self.ENRICHMENT_COLUMNS
                    }
                    self._fetched = fetched
                    self._out = None
        return fetched

    def release_output(self) -> None:
        """Memoize the enrichment columns and drop the step-output
        reference.  The egress calls this before handing the view to
        async consumers whenever the store path didn't already fetch —
        a view parked in a lagging outbound queue must never pin the
        step's device buffers."""
        self._enrichment()

    def __getitem__(self, name: str) -> np.ndarray:
        if name in self._ENRICH_SET:
            return self._enrichment()[name]
        if name in self._HOST_SET and name in self._host:
            return self._host[name]
        raise KeyError(name)

    def __contains__(self, name) -> bool:
        return (name in self._ENRICH_SET
                or (name in self._HOST_SET and name in self._host))

    def __iter__(self):
        for name in self.HOST_COLUMNS:
            if name in self._host:
                yield name
        yield from self.ENRICHMENT_COLUMNS

    def __len__(self) -> int:
        return (sum(1 for n in self.HOST_COLUMNS if n in self._host)
                + len(self.ENRICHMENT_COLUMNS))


class PipelineDispatcher(LifecycleComponent):
    """Owns the ingest→step→egress loop for one instance.

    Collaborators are duck-typed providers so tenants/tests can compose
    subsets:

    - ``registry_provider()`` / ``zones_provider()`` / ``rules_provider()``
      → current device-resident epochs (RegistryMirror / RuleManager)
    - ``state_manager`` → DeviceStateManager (commit + sweeps)
    - ``event_store`` → accepted-row persistence (append_columns)
    - ``outbound`` → OutboundConnectorsManager (submit cols+mask)
    - ``on_command_rows(cols, idx, trace=None)`` → command-delivery hook
      (``trace`` is the plan's trace so the delivery span joins it)
    - ``registration`` → RegistrationManager (process_unregistered)
    """

    def __init__(
        self,
        batcher: Batcher,
        registry_provider: Callable[[], object],
        state_manager,
        rules_provider: Callable[[], object],
        zones_provider: Callable[[], object],
        event_store=None,
        outbound=None,
        registration=None,
        on_command_rows: Optional[Callable[..., None]] = None,
        analytics=None,
        rules_engine=None,
        journal: Optional[Journal] = None,
        dead_letters: Optional[Journal] = None,
        resolve_tenant: Optional[Callable[[str], int]] = None,
        on_host_request: Optional[Callable[[DecodedRequest, bytes], None]] = None,
        max_replay_depth: int = 4,
        inflight_depth: Optional[int] = None,
        mesh=None,
        journal_reader: Optional[JournalReader] = None,
        recovery_decoder: Optional[Callable[[bytes], List[DecodedRequest]]] = None,
        tracer=None,
        metrics=None,
        egress_offload: Optional[bool] = None,
        overload=None,
        ring_depth: Optional[int] = None,
        flightrec=None,
        slo=None,
        breaker=None,
        watchdog=None,
        quarantine_after: int = 3,
        cost_analysis: Optional[bool] = None,
        usage_ledger=None,
        name: str = "pipeline-dispatcher",
    ):
        super().__init__(name)
        self.batcher = batcher
        self.registry_provider = registry_provider
        self.rules_provider = rules_provider
        self.zones_provider = zones_provider
        self.state_manager = state_manager
        self.event_store = event_store
        self.outbound = outbound
        self.registration = registration
        self.on_command_rows = on_command_rows
        # Streaming analytics (analytics/runner.QueryRunner): egress
        # offers every accepted enriched batch via a NON-blocking
        # bounded queue — live CEP/window queries evaluate on the
        # runner's own worker, never on the egress path's budget.
        self.analytics = analytics
        # Bring-your-own-rules engine (rules/engine.RuleEngineRunner):
        # same egress offer discipline as analytics — non-blocking
        # bounded queue, compiled tenant programs evaluate on the
        # engine's own worker, fired programs re-enter through
        # inject_rule_alerts below.
        self.rules_engine = rules_engine
        self.journal = journal
        self.dead_letters = dead_letters
        self.resolve_tenant = resolve_tenant or (lambda token: 0)
        # host-plane requests (device streams) decoded off the wire path
        self.on_host_request = on_host_request
        # Overload admission gate (runtime/overload.py): the LIVE intake
        # edges (ingest / ingest_many / ingest_wire_decoded) consult it
        # BEFORE journaling; shed rows dead-letter (kind "intake-shed")
        # and a fully-shed payload raises OverloadShed so the receiving
        # transport signals protocol-native backpressure.  Recovery
        # paths (journal replay, derived re-injection, ingest_arrays)
        # deliberately bypass it — already-journaled work is never shed.
        self.overload = overload
        self.max_replay_depth = max_replay_depth
        # Donate the step's carry only where donation is real: the state
        # manager hands its epoch over under its lock (step_packed for a
        # single step, lease_packed for the ring's chain), so no reader
        # can hold the buffers a donating program deletes.  Off on the
        # CPU backend, where a carry is cheap to copy.
        self._ring_donate = jax.default_backend() != "cpu"
        self.mesh = mesh
        if mesh is not None:
            # Multi-chip: shard_map step over the mesh (Kafka-partitioning
            # analog, SURVEY.md §2.4) — the batcher already routes each row
            # to the sub-batch of the shard owning its registry block.
            # The packed mesh form: per-call placement cost on a mesh
            # scales with buffer count × hosts (build_sharded_packed_step).
            from sitewhere_tpu.pipeline.sharded import (
                build_sharded_packed_step,
                place_packed_state,
            )

            self._packed_step = build_sharded_packed_step(
                mesh, donate=self._ring_donate)
            # lays an epoch out on the mesh before a step or a chain
            self._place_state = functools.partial(place_packed_state, mesh)
        else:
            # Single chip: the packed step moves ~11 buffers per call
            # instead of ~110 — per-call dispatch scales with buffer
            # count (pipeline/packed.py).  Donated where donation is
            # real: the scatter writes the named rows in place instead
            # of into a copy of the whole carry (PERF.md §5).
            from sitewhere_tpu.pipeline.packed import build_packed_step

            self._packed_step = build_packed_step(donate=self._ring_donate)
            self._place_state = None
        from sitewhere_tpu.pipeline.packed import pack_tables

        self._pack_tables = jax.jit(pack_tables)
        self._tables_cache: Optional[tuple] = None
        # Commit-after-egress stream position (Kafka manual-commit analog,
        # MicroserviceKafkaConsumer.java:94): the highest journal offset
        # whose row has completed egress.  Committed only at quiescent
        # points (no pending rows, no in-flight step) so an earlier offset
        # still queued in another shard segment can never be skipped.
        self.journal_reader = journal_reader
        # Decoder for journaled wire payloads on crash recovery — MUST
        # match what the instance's sources journal (JSON by default; a
        # deployment with binary/composite sources passes its own).
        self.recovery_decoder = recovery_decoder
        self._max_egressed_ref = -1
        # Crash-recovery store dedup (runtime/checkpoint.py offset
        # contract): rows whose journal offset is below this floor are
        # durably in the event store already (the commit gate seals
        # BEFORE the offset commits), so a replay that starts below the
        # committed offset — rebuilding volatile component state from an
        # older snapshot — re-runs their state/analytics effects WITHOUT
        # duplicating persistence.  0 = inactive; set by replay_journal.
        self.store_dedup_floor = 0
        # Plans emitted by the batcher whose egress has not completed.
        # Guarded by _lock; the commit gate requires it to be zero so a
        # plan sitting between emission and _run_plan (outside both
        # batcher.pending and _inflight) can never be committed past.
        self._plans_outstanding = 0
        # Of those, the plans whose egress raised: they stay outstanding
        # (the gate fails closed until a restart replays them), and no
        # wait can end them, so flush() stops waiting for them.
        self._plans_failed = 0
        self._lock = threading.Lock()
        # Serializes read-state → step → commit → egress across the loop
        # thread, source threads, and the presence thread: two concurrent
        # steps from the same snapshot would lose the first commit's state
        # merges.  RLock: replay/derived re-injection recurses.
        self._step_lock = threading.RLock()
        # FIFO of (plan, outputs, replay_depth, trace) steps dispatched but
        # not yet egressed; guarded by _step_lock.  Depth >1 keeps several
        # steps in flight so egress (a device→host fetch) overlaps later
        # steps' compute+transfers — a 1-deep window serializes the whole
        # wire path on each fetch's round trip.  The outputs' host copies
        # are started asynchronously at dispatch time
        # (copy_to_host_async), so by the time a plan reaches the egress
        # end of the window its bytes are already host-side.  Latency
        # stays bounded: the loop thread drains the window whenever no new
        # plan is due, so depth only manifests under sustained load —
        # exactly when per-plan latency is throughput-bound anyway.
        if inflight_depth is None or inflight_depth <= 0:
            inflight_depth = 8 if jax.default_backend() == "tpu" else 1
        self.inflight_depth = int(inflight_depth)
        # Device-resident dispatch ring (the promoted phase-C packed
        # chain): full-width packed plans collect in `_ring` until
        # `ring_depth` are staged, then ONE jitted K-step chain
        # (pipeline/packed.py build_packed_chain) steps them all with a
        # single host dispatch and — via the shared RingFetch — a single
        # D2H sync for the whole ring's egress.  None = backend-adaptive
        # (8 on TPU, off elsewhere); any value < 2 disables.  On a mesh
        # the SAME ring runs the sharded chain (pipeline/sharded.py
        # build_sharded_packed_chain): one SPMD program steps all K
        # slots across every shard, so the 1/K host-sync economy and the
        # mesh's aggregate throughput compose instead of excluding each
        # other.  Latency stays bounded: deadline/flush/replay plans —
        # and the loop thread, once the ring's oldest plan ages past the
        # batcher deadline — drain the ring through the single-step path
        # IN ORDER, so per-device event order is never reordered around
        # ring-held predecessors and an idle trickle degrades to exactly
        # the pre-ring behavior.
        if ring_depth is None or ring_depth < 0:
            from sitewhere_tpu.pipeline.packed import ring_depth_default

            ring_depth = ring_depth_default()
        self.ring_depth = int(ring_depth) if int(ring_depth) >= 2 else 0
        self._ring: List[BatchPlan] = []
        self._ring_chains: Dict[int, Callable] = {}
        # Ring-shaped dispatch scratch: the K slot references a chain
        # dispatch hands to the jitted call are written into these
        # preallocated lists (and cleared after the dispatch so staged
        # H2D buffers don't outlive their ring) — the steady-state chain
        # path allocates no per-dispatch K-length lists.
        self._ring_slots_i: List = [None] * self.ring_depth
        self._ring_slots_f: List = [None] * self.ring_depth
        if self.ring_depth:
            # the in-flight window must hold at least two rings so chain
            # N+1 dispatches while ring N's egress drains (double
            # buffering at ring granularity)
            self.inflight_depth = max(self.inflight_depth,
                                      2 * self.ring_depth)
        self._inflight: collections.deque = collections.deque()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Egress offload (overlapped host pipeline): between start() and
        # stop() a dedicated worker pulls dispatched steps off _inflight
        # and runs the host fan-out, so the ONLY work left on the
        # dispatch thread is batch assembly + jitted-step launch.  The
        # window doubles as the bounded offload queue: _run_plan stalls
        # (before taking the step lock — never while holding it, so the
        # worker cannot deadlock against a lock-holder) once egress falls
        # `egress_queue_depth` plans behind.  The worker runs under a
        # Supervisor: an egress crash is a worker death mid-window — the
        # failed plan stays outstanding (commit gate fails closed,
        # at-least-once replay recovers it) while the restarted worker
        # keeps draining its siblings.  Without start() (or with
        # egress_offload=False) every path degrades to the inline
        # synchronous egress, the pre-offload behavior.
        #
        # Default is backend-adaptive (same spirit as inflight_depth):
        # ON off-CPU, where egress blocks on device→host fetches with
        # the GIL released and the overlap is real; OFF on the CPU
        # backend, where the GIL serializes the stages anyway and the
        # offload's backpressure stalls read as idle to the adaptive
        # batcher.
        if egress_offload is None:
            egress_offload = jax.default_backend() != "cpu"
        self.egress_offload = bool(egress_offload)
        self.egress_queue_depth = max(2, self.inflight_depth)
        self._egress_super = None
        self._egress_busy = False
        self._egress_stop = threading.Event()
        self._egress_evt = threading.Event()   # work queued
        self._room_evt = threading.Event()     # slot freed
        self.egress_failures = 0
        # Per-plan end-to-end latency samples (oldest-row wait in the
        # batcher + emit→egress-complete), the <10ms p99 target's metric.
        self.latencies_s: collections.deque = collections.deque(maxlen=4096)
        # Span tracing (reference: Jaeger 1% sampling) — no-op when unset.
        if tracer is None:
            from sitewhere_tpu.runtime.tracing import Tracer

            tracer = Tracer(sample_rate=0.0)  # disabled unless configured
        self.tracer = tracer
        # Registry surface (the .prom exposition): instruments are bound
        # ONCE here so the per-plan path pays attribute loads, not dict
        # lookups.  Histogram observations carry the plan's trace id as
        # an exemplar when that trace was retained — the exposition links
        # a latency bucket to a concrete trace an operator can open.
        if metrics is None:
            from sitewhere_tpu.runtime.metrics import MetricsRegistry

            metrics = MetricsRegistry()
        self.metrics = metrics
        self._m_e2e = metrics.histogram("pipeline.e2e_latency_s")
        self._m_assemble = metrics.histogram("pipeline.batch_assemble_s")
        self._m_steps = metrics.counter("pipeline.steps")
        # single steps dispatched under pipeline.width: partial plans on
        # a narrow rung of the batcher's width ladder (plan_rungs)
        self._m_steps_narrow = metrics.counter("pipeline.steps_narrow")
        # single steps dispatched with the carry donated (_step_epoch)
        self._m_steps_donated = metrics.counter("pipeline.steps_donated")
        # Per-stage host-time timers (the overlapped-pipeline instrument
        # surface): decode / batch-assembly / step-dispatch / egress each
        # accumulate the HOST time they consume, so `sum(stage totals) >
        # wall elapsed` is the measurable proof the stages overlap.
        # Lexical stages go through ``Timer.time(seq=plan.seq)``: the
        # same region is a span of the timer's name in a profiler
        # capture.  Intervals between threads (ring_wait, inflight_wait)
        # are plain ``observe()``s.
        self._m_stage = {
            s: metrics.timer(f"pipeline.stage_{s}_s")
            for s in ("decode", "batch", "dispatch", "egress",
                      # ring stages: per-slot wait before its chain
                      # launches, and the chain's host dispatch cost
                      "ring_wait", "ring_dispatch",
                      # the dispatch path blocked: a full egress window
                      # (_stall_for_egress_room) plus the wait for
                      # _step_lock, ending when the lock is held
                      "dispatch_wait",
                      # a dispatched plan queued in _inflight until the
                      # egress worker (or an inline drain) pops it
                      "inflight_wait")
        }
        if mesh is not None:
            # a packed plan's per-shard placement (_stage_packed: two
            # arrays x n_shards device_puts); never observed on one chip
            self._m_stage["place"] = metrics.timer("pipeline.stage_place_s")
        # the per-tenant fold of one egressed plan into the usage ledger
        # (_meter_plan); observed only where metering is on
        self._m_stage["meter"] = metrics.timer("pipeline.stage_meter_s")
        # rows taken through the wire intake, and those of them under a
        # tenant other than ``default`` (ingest_wire_lines' ``tenant``)
        self._m_wire_rows = metrics.counter("ingest.wire_rows")
        self._m_wire_rows_tenant = metrics.counter("ingest.wire_rows_tenant")
        # The host BLOCKED on the device finishing a step and on its D2H
        # (the views' blocking fetch in pipeline/packed.py): a child of
        # the egress stage, so egress self time = stage_egress_s -
        # device_wait_s.
        # One observation per host sync (pipeline.host_syncs).
        self._m_device_wait = metrics.timer("pipeline.device_wait_s")
        # "How often does the host touch the device" as a first-class
        # metric: one inc per BLOCKING device→host sync on the dispatch/
        # egress path (the packed views' lazy fetch, the ring's shared
        # fetch).  The ring's whole point is host_syncs/steps → 1/K.
        self._m_host_syncs = metrics.counter("pipeline.host_syncs")
        # Zero-copy ingest evidence: bytes memcpy'd per host stage.  The
        # fill-direct wire path contributes ZERO to decode (the C scan
        # writes once, into the batcher's packed rows) and an adopted
        # full-width reservation contributes zero to batch — measured
        # here, not asserted.  h2d counts the staged transfer bytes.
        self._m_bytes = {
            key: metrics.counter(f"pipeline.bytes_copied.{key}")
            for key in ("decode", "batch", "h2d")
        }
        # Decodes that raced the seconds-long first-use native build and
        # silently took the Python path (native/__init__.py counter,
        # sampled by the loop thread).
        self._m_native_fb = metrics.gauge("native.build_fallbacks")
        # Fill-direct wire decode (zero-copy native ingest).  SW_NATIVE=0
        # still disables the whole native tier; SW_NATIVE_FILL=0 keeps
        # the classic native scanners but turns the fill-direct path off
        # (the bench's A/B knob).
        self._fill_enabled = os.environ.get("SW_NATIVE_FILL", "1") != "0"
        self._m_ring_chains = metrics.counter("pipeline.ring_chains")
        self._m_ring_flushes = metrics.counter("pipeline.ring_flushes")
        self._m_host_copy_err = metrics.counter("pipeline.host_copy_errors")
        self._m_egress_fail = metrics.counter("pipeline.egress_failures")
        self._m_stall_overflow = metrics.counter(
            "pipeline.egress_stall_overflows")
        self._m_queue = metrics.gauge("ingest.queue_depth")
        self._m_inflight = metrics.gauge("pipeline.inflight_steps")
        self._m_seal = metrics.gauge("pipeline.ingest_to_seal_latency_s")
        self._m_totals = {
            key: metrics.counter(f"pipeline.events_{key}")
            for key in ("processed", "accepted", "unregistered",
                        "unassigned", "threshold_alerts", "zone_alerts")
        }
        # Flight recorder (runtime/flightrec.py): one structured record
        # per egressed batch, dumped to JSONL on anomaly — egress-worker
        # crash here, overload transitions and SLO burn alerts via the
        # instance wiring.  None = recording off (tests composing bare
        # dispatchers).
        self.flightrec = flightrec
        # SLO burn-rate engine (runtime/metrics.py BurnRateEngine): the
        # loop thread ticks it alongside the overload controller.
        self.slo = slo
        # On-device occupancy telemetry (pipeline/packed.py
        # TELEMETRY_SCALARS rides the packed metrics vector — zero extra
        # host syncs), surfaced as last-batch gauges.
        self._m_occ = {
            key: metrics.gauge(f"device.occupancy.{key}")
            for key in ("rows_admitted", "rows_invalid", "rules_fired",
                        "state_writes", "presence_merges")
        }
        # Device-tier fault containment (runtime/devguard.py + the
        # _recover_ring/_contain_step_failure paths below).  The metric
        # families are declared closed in analysis/metric_names.py —
        # device.* is a governed prefix.
        self._m_fault = {
            key: metrics.counter(f"device.fault.{key}")
            for key in ("chain_faults", "step_faults", "bisect_rounds",
                        "poison_rows", "releases", "breaker_trips",
                        "watchdog_soft_trips", "watchdog_hard_trips",
                        "host_copy_faults", "cpu_fallback_steps")
        }
        self._m_breaker_state = metrics.gauge("device.fault.breaker_state")
        self._m_quar_devices = metrics.gauge("pipeline.quarantine.devices")
        self._m_quar_rows = metrics.counter(
            "pipeline.quarantine.rows_nonfinite")
        self._m_quar_changes = metrics.counter(
            "pipeline.quarantine.state_changes")
        from sitewhere_tpu.runtime.devguard import (
            DeviceBreaker,
            DeviceWatchdog,
            ShardBreakers,
        )

        # Breaker: repeated device faults across distinct batches demote
        # dispatch chained → single-step → CPU fallback; a cooldown
        # probe restores.  Watchdog: wall-clock budgets over in-flight
        # dispatches; past the hard budget the tier is unhealthy and the
        # flag rides the heartbeat (instance wiring).  Callers may pass
        # pre-configured guards (thresholds/clock); the dispatcher
        # attaches its own handlers to any that were left unset.
        #
        # Mesh dispatch gets a PER-SHARD breaker bank: a fault
        # attributed to one shard's batch segment demotes that shard
        # alone — its rows are masked out of the chain and side-routed
        # (_sidecar_shard_rows) while the healthy shards keep chaining.
        self._mesh_shards = (batcher.n_shards
                             if mesh is not None and batcher.n_shards > 1
                             else 0)
        # batch rows of shard s live at [s*seg, (s+1)*seg) — the
        # batcher's routed segment layout, the attribution key for
        # nonfinite-row → shard fault mapping
        self._shard_seg = (batcher.width // batcher.n_shards
                           if self._mesh_shards else 0)
        if breaker is not None:
            self.breaker = breaker
        elif self._mesh_shards:
            self.breaker = ShardBreakers(self._mesh_shards)
        else:
            self.breaker = DeviceBreaker()
        self._shard_breakers = hasattr(self.breaker, "demoted_shards")
        if self.breaker.on_trip is None:
            self.breaker.on_trip = (self._on_shard_breaker_trip
                                    if self._shard_breakers
                                    else self._on_breaker_trip)
        if self.breaker.on_restore is None:
            self.breaker.on_restore = (self._on_shard_breaker_restore
                                       if self._shard_breakers
                                       else self._on_breaker_restore)
        self.watchdog = (watchdog if watchdog is not None
                         else DeviceWatchdog())
        if self.watchdog.on_soft is None:
            self.watchdog.on_soft = self._on_watchdog_soft
        if self.watchdog.on_unhealthy is None:
            self.watchdog.on_unhealthy = self._on_watchdog_hard
        if self.watchdog.on_recovered is None:
            self.watchdog.on_recovered = self._on_watchdog_recovered
        # Shard-scoped wedge attribution: when the hard budget trips on
        # a mesh, the breaker bank's suspect shards are recorded here
        # and ride the heartbeat (device_unhealthy_shards) so peers can
        # park forwards for the sick shard's device range only.  Cleared
        # when the watchdog recovers.
        self._unhealthy_shards: tuple = ()
        # NaN/Inf quarantine: host policy over the device-counted
        # rows_nonfinite telemetry scalar.  The per-device attribution
        # scan runs ONLY when a plan's scalar is nonzero (the rare
        # path); a device crossing `quarantine_after` cumulative poison
        # rows emits one STATE_CHANGE through normal egress.
        self.quarantine_after = max(1, int(quarantine_after))
        self._nonfinite_seen: Dict[int, int] = {}
        self._quarantined: set = set()
        # D2H copy-fault escalation: _on_host_copy_error flags the
        # suspect; the egress failure that follows re-dispatches the
        # plan single-step instead of surfacing the secondary fetch
        # error as an unexplained egress crash.
        self._copy_suspect = False
        # Watchdog tokens per dispatched plan, keyed by id(plan) —
        # BatchPlan has __slots__, and the token is dispatch-scoped
        # bookkeeping, not plan state.
        self._wd_tokens: Dict[int, int] = {}
        self._cpu_step = None   # lazily-built FALLBACK-level step
        # the boot warm-up's failure, if any (see _warm_programs)
        self.warm_error: Optional[BaseException] = None
        # XLA cost analysis of the compiled chain at warm-up (flops /
        # bytes as device.cost.* gauges — the static roofline half).
        # Backend-adaptive default: the AOT lower+compile costs a second
        # compile, which boot absorbs on TPU but tier-1 CPU runs (where
        # the ring is forced on for smoke coverage) should not pay.
        if cost_analysis is None:
            cost_analysis = jax.default_backend() != "cpu"
        self.cost_analysis = bool(cost_analysis)
        # Tenant metering plane (runtime/metering.py UsageLedger): egress
        # folds each plan's device-side per-tenant scatter block into it
        # (_meter_plan) — the block rides the same fetched metrics
        # vector as TELEMETRY_SCALARS, so attribution costs zero extra
        # host syncs.  None = metering off (bare test dispatchers).
        self.usage_ledger = usage_ledger
        # decode-stage attribution mark: egress is serialized per plan,
        # so the delta of the decode timer's running total between
        # meter calls is the decode time this plan's rows paid for
        self._meter_decode_mark = 0.0
        # host-aggregated counters (metrics endpoint surface)
        self.steps = 0
        self.totals: Dict[str, int] = {
            "processed": 0, "accepted": 0, "unregistered": 0,
            "unassigned": 0, "threshold_alerts": 0, "zone_alerts": 0,
            "replayed": 0, "derived_alerts": 0, "commands": 0,
        }

    def step_barrier(self):
        """The lock serializing read-state → step → commit.  Out-of-band
        state writers (ownership migration imports) hold it so an
        in-flight step computed from the pre-write epoch cannot clobber
        their rows at commit time."""
        return self._step_lock

    # -- ingest entry points (wired as InboundEventSource.on_event) ---------

    def _take(self, intake: Callable[[], object]) -> List[BatchPlan]:
        """Run a batcher intake under the lock, counting every emitted plan
        as outstanding until its egress completes — the commit gate's
        accounting (see ``_maybe_commit_offset``)."""
        with self._m_stage["batch"].time() as span:
            with self._lock:
                out = intake()
                if out is None:
                    plans: List[BatchPlan] = []
                elif isinstance(out, list):
                    plans = [p for p in out if p is not None]
                else:
                    plans = [out]
                self._plans_outstanding += len(plans)
            if not plans:
                span.discard()   # the timer is per EMITTED plan
        return plans

    def _run_plans(self, plans: List[BatchPlan],
                   replay_depth: int = 0) -> None:
        """Stage every plan's H2D transfer up front, then step them —
        with 2+ plans from one intake the later transfers overlap the
        earlier steps (the double-buffer across a burst)."""
        for plan in plans:
            self._stage_plan(plan)
        for plan in plans:
            self._run_plan(plan, replay_depth)

    def _stage_packed(self, bi, bf, seq: Optional[int] = None):
        """One packed batch placed where the jitted programs take it:
        ``device_put`` ahead of its step on one chip (None on the CPU
        backend — the jitted call then transfers synchronously), or on a
        mesh split along its width into ``n_shards`` segments, each put
        on the chip that owns those devices' registry rows
        (``place_packed_batch``: two arrays x ``n_shards`` transfers).
        The transfers are asynchronous, so a burst's later placements
        overlap earlier steps on either path.  The mesh placement of a
        plan (``seq`` given; the boot warm-up passes none) is timed as
        ``pipeline.stage_place_s`` — the host's time to issue the
        transfers, one observation a packed plan."""
        if self.mesh is not None:
            from sitewhere_tpu.pipeline.sharded import place_packed_batch

            if seq is None:
                return place_packed_batch(self.mesh, bi, bf)
            with self._m_stage["place"].time(seq=seq):
                return place_packed_batch(self.mesh, bi, bf)
        from sitewhere_tpu.pipeline.packed import stage_packed_batch

        return stage_packed_batch(bi, bf)

    def _stage_plan(self, plan: BatchPlan) -> None:
        """Start the async H2D copy of a plan (double-buffer front half,
        :meth:`_stage_packed`)."""
        if plan.staged is None:
            plan.staged = self._stage_packed(plan.packed_i, plan.packed_f,
                                             seq=plan.seq)
            if plan.staged is not None:
                self._m_bytes["h2d"].inc(
                    plan.packed_i.nbytes + plan.packed_f.nbytes)

    def _shed_intake(self, payload: bytes, shed: Dict[object, int],
                     source_id: str, tenant: str,
                     budget_bound: bool = False) -> None:
        """Audit one intake shed: dead-letter the payload with reason +
        per-class counts so shedding is inspectable AND replayable
        (``requeue_dead_letter`` re-drives it like a failed decode once
        the overload clears).  Sheds the tenant's CONFIGURED budget
        overlay caused carry their own kind ``tenant-budget`` (with the
        budget that clipped them) — distinct from the generic
        ``intake-shed``, so an operator can tell "the fleet was
        overloaded" from "this tenant outran the budget it bought";
        replay re-applies the tenant's CURRENT budget either way."""
        doc = {
            "kind": "tenant-budget" if budget_bound else "intake-shed",
            "state": self.overload.state.name,
            "reason": ("tenant budget exceeded" if budget_bound
                       else self.overload.last_driver or "admission"),
            "classes": {cls.name.lower(): int(n)
                        for cls, n in shed.items()},
            "source": source_id,
            "tenant": tenant,
            "payload": payload.hex(),
        }
        if budget_bound:
            overlay = self.overload.tenant_budgets.overlay(tenant)
            if overlay:
                doc["budget"] = overlay
        dead_letter(self.dead_letters, doc)
        if self.usage_ledger is not None:
            try:
                self.usage_ledger.charge(
                    self.resolve_tenant(tenant), "dead_letter_rows",
                    sum(shed.values()))
            except Exception:
                logger.exception("dead-letter usage charge failed")

    def _admit_requests(self, reqs: List[DecodedRequest], payload: bytes,
                        source_id: str) -> List[DecodedRequest]:
        """Admission-filter a decoded request list.  Returns the admitted
        subset; sheds are dead-lettered once per payload.  Raises
        :class:`OverloadShed` when NOTHING was admitted — the caller's
        transport turns that into native backpressure."""
        from sitewhere_tpu.runtime.overload import classify_event_type

        admitted: List[DecodedRequest] = []
        shed: Dict[object, int] = {}
        worst = None
        budget_bound = False
        for req in reqs:
            cls = classify_event_type(int(req.event_type))
            tenant = (req.metadata.get("tenant", "default")
                      if req.metadata else "default")
            ok, reason = self.overload.admit_detail(
                cls, tenant=tenant, source=source_id)
            if ok:
                admitted.append(req)
            else:
                shed[cls] = shed.get(cls, 0) + 1
                worst = cls
                budget_bound = budget_bound or reason == "budget"
        if shed:
            tenant = (reqs[0].metadata.get("tenant", "default")
                      if reqs[0].metadata else "default")
            self._shed_intake(payload, shed, source_id, tenant,
                              budget_bound=budget_bound)
        if not admitted and shed:
            raise self.overload.shed_exception(worst)
        return admitted

    def ingest(self, req: DecodedRequest, payload: bytes = b"",
               source_id: str = "ingest") -> None:
        """Queue one decoded request (journal it first: at-least-once)."""
        if self.overload is not None and req.event_type is not None:
            req = self._admit_requests([req], payload, source_id)[0]
        ref = NULL_ID
        if self.journal is not None and payload:
            ref = self.journal.append(payload)
        tenant_id = self.resolve_tenant(req.metadata.get("tenant", "default")
                                        if req.metadata else "default")
        self._run_plans(self._take(
            lambda: self.batcher.add(req, tenant_id=tenant_id,
                                     payload_ref=ref)))

    def ingest_many(self, reqs: List[DecodedRequest],
                    payload: bytes = b"",
                    source_id: str = "ingest") -> None:
        """Columnar intake of one wire payload's decoded events (the
        batch-decoder fast path): one resolution pass, no per-row
        dataclass churn, and the payload journals ONCE — every row shares
        the offset, so replay decodes it a single time (at-least-once,
        like the reference's record-level Kafka redelivery)."""
        if not reqs:
            return
        # Validate BEFORE journaling so a host-plane request in the batch
        # can't leave an orphaned journal record behind a raised error.
        for r in reqs:
            if r.event_type is None:
                raise ValueError(
                    f"{r.kind.name} is a host-plane request, not a pipeline event"
                )
        if self.overload is not None:
            # admission before the journal append: shed rows are dead-
            # lettered (replayable), never journaled — a fully shed
            # payload raises so the transport signals backpressure
            reqs = self._admit_requests(reqs, payload, source_id)
            if not reqs:
                return
        ref = NULL_ID
        if self.journal is not None and payload:
            ref = self.journal.append(payload)
        tenants = [
            self.resolve_tenant(r.metadata.get("tenant", "default")
                                if r.metadata else "default")
            for r in reqs
        ]
        self._run_plans(self._take(
            lambda: self.batcher.add_requests(reqs, tenants,
                                              [ref] * len(reqs))))

    def ingest_arrays(self, **columns) -> None:
        """Pre-resolved columnar intake (dense handles, no string work):
        the highest-rate edge, fed by vectorized decoders or re-injection.
        Accepts the :mod:`sitewhere_tpu.ingest.batcher` column set; rows
        without an explicit ``tenant_id`` land in the default tenant (the
        scalar ``ingest`` path's behavior)."""
        if "tenant_id" not in columns:
            n = len(columns["device_id"])
            columns["tenant_id"] = np.full(
                n, self.resolve_tenant("default"), np.int32)
        self._run_plans(self._take(
            lambda: self.batcher.add_arrays(**columns)))

    def ingest_wire_lines(self, payload: bytes, source_id: str = "wire",
                          raise_on_decode_error: bool = False,
                          tenant: str = "default") -> int:
        """Columnar NDJSON wire intake: bytes → column arrays → batcher.

        The true 1M events/sec edge (round-2 verdict weak #2): ONE
        C-level JSON parse for the whole payload, one sweep per field, no
        per-event ``DecodedRequest`` objects, one journal record shared by
        every row.  Host-plane lines (registrations) take the scalar
        path; an undecodable payload dead-letters whole.  Returns the
        number of event rows accepted into the batcher.

        ``tenant`` is the token of the tenant the payload came in FOR —
        its source's, as upstream's is the Kafka topic's
        (``…tenant.<id>.event-source-decoded-events``), not a field of
        the line: every row is stamped with ``resolve_tenant(tenant)``,
        admission sheds and bills under it, and the journal record keeps
        it so that a replay lands the rows in the same tenant.  The step
        takes a row only when that tenant owns its device; a row of
        another tenant's device is refused ``unregistered`` and
        dead-lettered.  A token no tenant has is not an error here, as
        it is none on the scalar path's ``metadata.tenant``:
        ``resolve_tenant`` mints an id that owns no device, so every row
        is refused the same way and none is taken under ``default``.
        """
        from sitewhere_tpu.ingest.decoders import DecodeError

        try:
            columns, host_reqs = self.decode_wire_lines(payload)
        except DecodeError as e:
            # raise_on_decode_error: a raw_wire source wants the error
            # back so ITS failure counter ticks and ITS on_failed_decode
            # dead-letters (once) — same observable path as the scalar
            # decoder's failures
            if raise_on_decode_error:
                raise
            self.ingest_failed_decode(payload, source_id, e)
            return 0
        return self.ingest_wire_decoded(payload, columns, host_reqs,
                                        source_id=source_id, tenant=tenant)

    def decode_wire_lines(self, payload: bytes):
        """The pure DECODE stage of :meth:`ingest_wire_lines` — no
        journal append, no state mutation, so a decode-pool worker can
        run it for window N+1 while window N is on device.  Raises
        :class:`DecodeError`; returns ``(columns, host_requests)``.

        Fill-direct fast path: resolved measurement payloads scan
        STRAIGHT into a private batcher reservation (zero intermediate
        copies; the reservation rides the ``columns`` slot through the
        decode pool and commits in delivery order at
        :meth:`ingest_wire_decoded`).  Any shape deviation falls back to
        :func:`decode_json_lines` bit-for-bit, errors included.
        """
        from sitewhere_tpu.ingest.columnar import (
            CopyTally,
            decode_fill_direct,
            decode_json_lines,
            fill_direct_ready,
            space_of,
        )

        with self._m_stage["decode"].time():
            space = space_of(self.batcher.resolve_device)
            if space is not None and self._fill_enabled \
                    and fill_direct_ready(payload, space):
                res = self.batcher.reserve(payload.count(b"\n") + 1)
                if res is not None and decode_fill_direct(
                        payload, space, res,
                        self.batcher.resolve_mtype) is not None:
                    return res, []
            tally = CopyTally()
            out = decode_json_lines(payload, device_space=space,
                                    copied=tally)
            if tally.n:
                self._m_bytes["decode"].inc(tally.n)
            return out

    def _admit_columns(self, columns, payload: bytes, source_id: str,
                       tenant: str = "default"):
        """Admission-filter one decoded wire-column dict (vectorized:
        one fancy-index classifies every row, one bucket take per class
        per payload) under the payload's ``tenant``: its budget overlay
        clips it, its shed counter and ledger are billed.  Returns
        ``(admitted_columns, shed_classes)`` —
        columns may be the input unchanged, or None for zero admitted
        rows; dead-letters sheds; raising is the CALLER's decision
        (host-plane lines may still make the payload partially
        useful)."""
        from sitewhere_tpu.ingest.columnar import n_rows
        from sitewhere_tpu.runtime.overload import (
            CLASS_OF_EVENT_TYPE,
            PriorityClass,
        )

        n = n_rows(columns)
        if n == 0:
            return columns, {}
        et = np.asarray(columns["event_type"])
        class_of = np.fromiter(
            (int(c) for c in CLASS_OF_EVENT_TYPE), np.int32,
            len(CLASS_OF_EVENT_TYPE))
        # out-of-range types (STATE_CHANGE, future kinds) classify as
        # COMMAND — same default as classify_event_type; a bare clip
        # would alias them onto the last slot (COMMAND_RESPONSE →
        # CRITICAL) and exempt them from shedding entirely
        in_range = (et >= 0) & (et < len(class_of))
        classes = np.where(
            in_range, class_of[np.clip(et, 0, len(class_of) - 1)],
            np.int32(int(PriorityClass.COMMAND)))
        keep = np.ones(n, bool)
        shed: Dict[object, int] = {}
        budget_bound = False
        for cls in (PriorityClass.TELEMETRY, PriorityClass.COMMAND):
            m = classes == int(cls)
            count = int(m.sum())
            if count:
                ok, reason = self.overload.admit_detail(
                    cls, tenant=tenant, source=source_id, n=count)
                if not ok:
                    keep &= ~m
                    shed[cls] = count
                    budget_bound = budget_bound or reason == "budget"
        if not shed:
            return columns, shed
        self._shed_intake(payload, shed, source_id, tenant,
                          budget_bound=budget_bound)
        if not keep.any():
            return None, shed
        # decoded columns mix ndarrays (event_type, ts, values) and
        # python lists (device_token, mtype, alert_type) — filter every
        # length-n sequence, pass scalars/None through untouched
        rows = np.nonzero(keep)[0]

        def _filter(value):
            if isinstance(value, np.ndarray) and value.ndim >= 1 \
                    and len(value) == n:
                return value[keep]
            if isinstance(value, (list, tuple)) and len(value) == n:
                return [value[i] for i in rows]
            return value

        return ({key: _filter(value) for key, value in columns.items()},
                shed)

    def ingest_wire_decoded(self, payload: bytes, columns,
                            host_reqs, source_id: str = "wire",
                            tenant: str = "default") -> int:
        """The ordered INGEST tail of :meth:`ingest_wire_lines`: journal
        once (with the payload's ``tenant``), route host-plane lines,
        resolve + batch the event rows under that tenant.
        Must run in per-source submission order (the decode pool's
        delivery contract) so per-device event order and the journal's
        offset↔row correspondence are preserved."""
        from sitewhere_tpu.ingest.batcher import Reservation

        if isinstance(columns, Reservation):
            return self._ingest_reserved(payload, columns, source_id,
                                         tenant)
        if self.overload is not None:
            columns, shed = self._admit_columns(columns, payload, source_id,
                                                tenant)
            if columns is None:
                if host_reqs:
                    columns = {}   # host-plane lines still route below
                else:
                    # the WHOLE payload was shed: native backpressure,
                    # attributed to the most-privileged class refused
                    raise self.overload.shed_exception(
                        min(shed, key=int))
        # Decode validated the payload — journal once (at-least-once).
        ref = NULL_ID
        if self.journal is not None and payload:
            ref = self.journal.append(payload, tenant=tenant)
            # chaos kill point: journaled, never batched — the record is
            # the durable truth and MUST reappear via replay
            faults.crosspoint("crash.post_journal")
        from sitewhere_tpu.ingest.decoders import RequestKind

        for req in host_reqs:
            if tenant != "default":
                # a host-plane line of a tenant's payload is that
                # tenant's, like its event rows
                req.metadata = {"tenant": tenant, **(req.metadata or {})}
            if req.kind == RequestKind.REGISTRATION:
                self.ingest_registration(req, b"")
            elif self.on_host_request is not None:
                # device-stream requests (and other host-plane lines)
                # route to the instance handler — this is also how a
                # FORWARDED stream request is handled at its owning host
                self.on_host_request(req, payload)
            elif self.dead_letters is not None:
                # they must never silently mint devices via registration
                dead_letter(self.dead_letters, {
                    "kind": "unsupported-wire-line",
                    "request_kind": req.kind.name,
                    "device_token": req.device_token,
                    "payload_ref": int(ref),
                })
        if not columns:
            return 0   # every event row was shed; host-plane lines routed
        return self._ingest_resolved_columns(columns, ref, tenant)

    def _ingest_reserved(self, payload: bytes, res, source_id: str,
                         tenant: str = "default") -> int:
        """Ordered ingest tail of the fill-direct path: admission, ONE
        journal append, the per-payload constants, then commit under the
        intake lock.  Every scanned row is a MEASUREMENT (the resolved
        scanner accepts nothing else), so admission is exactly the
        whole-payload TELEMETRY decision the vector path would make —
        same audit record, same backpressure exception, under the
        payload's ``tenant``."""
        n = res.n
        if self.overload is not None:
            from sitewhere_tpu.runtime.overload import PriorityClass

            ok, reason = self.overload.admit_detail(
                PriorityClass.TELEMETRY, tenant=tenant, source=source_id,
                n=n)
            if not ok:
                res.abort()
                self._shed_intake(payload, {PriorityClass.TELEMETRY: n},
                                  source_id, tenant,
                                  budget_bound=reason == "budget")
                raise self.overload.shed_exception(PriorityClass.TELEMETRY)
        ref = NULL_ID
        if self.journal is not None and payload:
            ref = self.journal.append(payload, tenant=tenant)
            # chaos kill point: same contract as ingest_wire_decoded's
            faults.crosspoint("crash.post_journal")
        res.set_const(tenant_id=self.resolve_tenant(tenant),
                      payload_ref=ref)
        self._count_wire_rows(n, tenant)
        self._run_plans(self._take(res.commit))
        return n

    def _count_wire_rows(self, n: int, tenant: str) -> None:
        self._m_wire_rows.inc(n)
        if tenant != "default":
            self._m_wire_rows_tenant.inc(n)

    def _ingest_resolved_columns(self, columns, ref: int,
                                 tenant: str = "default") -> int:
        """Resolve one decoded column dict and queue its rows (shared by
        live wire intake and columnar journal replay — replay's
        equivalence argument depends on both using THIS code: rows get
        ``ref`` as payload_ref and land in ``tenant``, which the live
        intake takes from its caller and the replay from the journal
        record)."""
        from sitewhere_tpu.ingest.columnar import n_rows, resolve_columns

        n = n_rows(columns)
        if n == 0:
            return 0
        cols = resolve_columns(
            columns,
            self.batcher.resolve_device,
            self.batcher.resolve_mtype,
            self.batcher.resolve_alert,
            invocations=self.batcher.invocations,
        )
        cols["payload_ref"] = np.full(n, ref, np.int32)
        cols["tenant_id"] = np.full(
            n, self.resolve_tenant(tenant), np.int32)
        self._count_wire_rows(n, tenant)
        self._run_plans(self._take(
            lambda: self.batcher.add_arrays(_copy=False, **cols)))
        return n

    def ingest_registration(self, req: DecodedRequest, payload: bytes = b"") -> None:
        if self.registration is not None:
            self.registration.handle_registration(req)

    def ingest_failed_decode(self, payload: bytes, source_id: str, error) -> None:
        if self.dead_letters is not None:
            dead_letter(self.dead_letters,
                        {"kind": "failed-decode", "source": source_id,
                         "error": str(error), "payload": payload.hex()})

    # -- the loop -----------------------------------------------------------

    def start(self) -> None:
        super().start()
        self._stop.clear()
        if self.egress_offload and self._egress_super is None:
            from sitewhere_tpu.runtime.resilience import (
                RetryPolicy,
                Supervisor,
            )

            self._egress_stop.clear()
            self._egress_super = Supervisor(
                f"{self.name}-egress", self._egress_worker,
                policy=RetryPolicy(initial_s=0.01, max_s=1.0),
                max_restarts=8, min_uptime_s=5.0,
                on_restart=self._on_egress_restart,
                metrics=self.metrics)
            self._egress_super.start()
        self._warm_programs()
        self._thread = threading.Thread(
            target=self._loop, name=f"{self.name}-loop", daemon=True
        )
        self._thread.start()

    def _warm_programs(self) -> None:
        """Compile the programs live traffic dispatches at boot — the
        single packed step at every width the batcher emits at (one
        rung a partial plan, ``Batcher.rungs``) and, with the ring on,
        the K-step chain — with all-invalid batches (a semantic no-op:
        zero valid rows touch no state), so the first REAL dispatch
        doesn't charge a jit compile (about a minute each at the shipped
        capacity) to live traffic's p99 and to the hung-step watchdog's
        budgets, whichever rung it is the first to need.
        Best-effort: a failure only defers the compile to the first
        dispatch, and stays readable in :attr:`warm_error`."""
        self.warm_error = None
        try:
            from sitewhere_tpu.pipeline.packed import BATCH_F, BATCH_I

            def staged(width: int):
                bi = np.zeros((len(BATCH_I), width), np.int32)
                bf = np.zeros((len(BATCH_F), width), np.float32)
                # staged as a live plan is: a slot that arrives with
                # another placement is another program
                return self._stage_packed(bi, bf) or (bi, bf)

            slots = [staged(width) for width in self.batcher.rungs]
            tables = self._tables_packed()
            k = self.ring_depth
            if k:
                bi, bf = slots[-1]   # the configured width
                chain = self._ring_chain(k)
                with self._step_lock:
                    # block=True: completion is forced BEFORE the commit,
                    # so an asynchronously-surfacing execution failure
                    # raises here (state manager still holds the
                    # pre-chain epoch) instead of poisoning the adopted
                    # epoch for every subsequent live dispatch
                    self._dispatch_chain(
                        chain, tables, [bi] * k, [bf] * k, block=True)
            # the single steps, through the state manager's hand-off as
            # a live step goes (a donating step consumes the carry it is
            # handed): zero valid rows leave the state as it was
            for slot in slots:
                jax.block_until_ready(
                    self._step_epoch(self._packed_step, tables, *slot))
            if k and self.cost_analysis:
                # static roofline of the compiled chain: flops/bytes as
                # device.cost.* gauges (AOT lower+compile of the same
                # shapes; best-effort, inside this try on purpose)
                from sitewhere_tpu.pipeline.telemetry import (
                    record_cost_metrics,
                    xla_cost_analysis,
                )

                cost = xla_cost_analysis(
                    chain, tables, self.state_manager.current_packed,
                    *([bi] * k), *([bf] * k))
                record_cost_metrics(self.metrics, cost)
        except Exception as e:
            self.warm_error = e
            logger.warning("warm-up failed (compile deferred to the "
                           "first dispatch)", exc_info=True)

    def _dispatch_chain(self, chain, tables, slots_i, slots_f,
                        block: bool = False):
        """ONE chained dispatch with the donation-aware state hand-off
        (shared by the live ring and the boot warm-up so the
        donation-sensitive commit semantics cannot diverge): leased +
        donated carry where donation is real, plain epoch + read_epoch
        commit otherwise.  ``block=True`` forces completion before the
        commit — warm-up only; the live path keeps dispatch async and
        relies on the fail-closed window for execution failures."""
        if self._ring_donate:
            ps, token = self.state_manager.lease_packed()
            if self._place_state is not None:
                # a freshly-materialized lease pack has no mesh layout
                # yet; device_put is a no-op once the planes already
                # carry it (every lease after the first chain)
                ps = self._place_state(ps)
            out = chain(tables, ps, *slots_i, *slots_f)
            if block:
                jax.block_until_ready(out)
            self.state_manager.commit_packed(
                out[0], present_now=out[3], lease_token=token)
        else:
            epoch = self.state_manager.current_packed
            ps = epoch
            if self._place_state is not None:
                ps = self._place_state(ps)
            out = chain(tables, ps, *slots_i, *slots_f)
            if block:
                jax.block_until_ready(out)
            self.state_manager.commit_packed(
                out[0], present_now=out[3], read_epoch=epoch)
        return out

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        self.flush()
        if self._egress_super is not None:
            # after flush: the offload queue is drained (or the gate is
            # wedged closed by a dead plan — either way nothing further
            # to hand the worker)
            self._egress_stop.set()
            self._egress_evt.set()
            self._egress_super.stop()
            self._egress_super = None
        super().stop()

    def _loop(self) -> None:
        # poll at half the (possibly adaptive) deadline, floored at 2 ms:
        # an idle instance whose window shrank to the floor must not spin
        # the loop thread at sub-millisecond cadence
        while not self._stop.wait(max(self.batcher.deadline_s / 2, 0.002)):
            try:
                from sitewhere_tpu import native as _native

                self._m_native_fb.set(_native.build_fallbacks)
                if self.overload is not None:
                    # sample the pressure signals + run the overload
                    # state machine (rate-limited inside tick)
                    self.overload.tick()
                if self.slo is not None:
                    # SLO burn-rate sample (rate-limited inside tick)
                    self.slo.tick()
                # Hung-step watchdog: dispatch is async, so this thread
                # stays live even with a wedged chain in flight — the
                # blocking fetch happens at egress, not here.
                self.watchdog.check()
                # Backpressure: with the in-flight window full, a deadline
                # tick would emit a PARTIAL plan behind `depth` queued
                # steps — it gains no latency and fragments the width.
                # Drain one slot instead; pending rows keep coalescing
                # toward full-width plans (the counts>=seg ingest path is
                # unaffected and self-paces the source thread).
                # NEVER block this thread on the step lock: a wedged
                # dispatch holds it for the whole hang, and the watchdog
                # check above is the only thing that can still observe
                # it — a blocking acquire here would cap the loop at ONE
                # check per wedge (exactly when budget trips matter).
                if not self._step_lock.acquire(blocking=False):
                    continue
                try:
                    full = len(self._inflight) >= self.inflight_depth
                finally:
                    self._step_lock.release()
                if full:
                    self._drain_inflight(max_n=1)
                    continue
                plans = self._take(self.batcher.poll)  # deadline emit
                if plans:
                    self._run_plans(plans)
                else:
                    # No new batch: age out a partial ring, then drain
                    # the deferred steps so egress latency stays bounded
                    # when traffic pauses.
                    self._flush_ring_if_due()
                    # the idle housekeeping below takes the step lock:
                    # try, never wait (the rule above) — a dispatch that
                    # took the lock since this tick's probe may be the
                    # wedged one; the next tick retries
                    if self._step_lock.acquire(blocking=False):
                        try:
                            self._drain_inflight()
                            self._maybe_commit_offset()
                        finally:
                            self._step_lock.release()
            except Exception:
                logger.exception("dispatch cycle failed")

    def flush(self, timeout_s: float = 10.0) -> None:
        """Force pending rows through; on return every row ingested
        BEFORE the call has completed egress (tests/shutdown contract).

        A plan the loop thread has taken but not yet run is in neither
        ``batcher.pending`` nor ``_inflight`` — only the plans-outstanding
        gate sees it — so flush waits for gate quiescence (bounded:
        concurrent sources can keep refilling under sustained traffic).
        A plan whose egress failed never completes: quiescence leaves it
        outstanding, and the commit below stays closed on it.
        """
        self._run_plans(self._take(self.batcher.flush))
        self._flush_ring()
        self._drain_inflight()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                quiesced = (self._plans_outstanding == self._plans_failed
                            and self.batcher.pending == 0)
            # _egress_busy outlives the outstanding decrement (the
            # worker's metrics/trace tail runs before its finally clears
            # the flag) — breaking on outstanding alone would let the
            # commit below bail on the busy guard with no retry, skipping
            # the FINAL offset commit on stop()
            if quiesced and not self._egress_busy:
                break
            # re-take: rows ingested since the first take must not rely on
            # the loop thread (stop() joins it BEFORE this flush)
            self._run_plans(self._take(self.batcher.flush))
            self._flush_ring()
            self._drain_inflight()
            time.sleep(0.001)
        self._maybe_commit_offset()

    def _maybe_commit_offset(self) -> None:
        """Durably commit journal progress at a quiescent point.

        Commit order matches the reference (Mongo buffer flush, THEN Kafka
        offset): the event store's in-memory buffer is sealed to disk
        first, so a crash after commit can never have dropped a row the
        offset claims is done.
        """
        reader = self.journal_reader
        if reader is None or self._max_egressed_ref < 0:
            return
        with self._step_lock:
            if self._inflight or self._egress_busy:
                return
            with self._lock:
                if self.batcher.pending > 0 or self._plans_outstanding > 0:
                    return
                upto = self._max_egressed_ref + 1
                if upto > reader.committed:
                    if self.event_store is not None:
                        self.event_store.flush()
                    reader.commit(upto)

    def replay_journal(self, decoder=None, max_records: int = 4096,
                       upto: Optional[int] = None,
                       from_offset: Optional[int] = None) -> int:
        """Re-ingest journal records past the committed offset (crash
        recovery, at-least-once — ``MicroserviceKafkaConsumer.java:116-139``).

        Records were journaled as raw wire payloads; they replay through
        ``decoder`` (default JSON) without re-journaling, keeping their
        original offsets as ``payload_ref``.  Undecodable records
        dead-letter.  ``upto`` (exclusive) bounds the replay — pass the
        journal end captured before live sources start so a racing fresh
        append is never double-ingested.  ``from_offset`` starts the
        replay BELOW the committed offset (the checkpoint restore's
        per-component replay floor): those records re-run state and
        analytics effects but skip event-store persistence (they are
        durably stored already — ``store_dedup_floor``).  Returns
        replayed event rows.
        """
        reader = self.journal_reader
        if reader is None:
            return 0
        from sitewhere_tpu.ingest.decoders import (
            DecodeError,
            JsonLinesDecoder,
        )

        # With the DEFAULT decoder, C-scanner-accepted payloads replay
        # columnar-ly (the strict scanners bail on metadata/extras, so
        # anything they accept is bit-identical under both paths — the
        # scalar decoder keeps handling everything else, including
        # per-request metadata tenants).  A custom recovery decoder
        # disables the fast path outright.  Either way a row lands in
        # the tenant its payload was accepted under: the journal record
        # keeps the wire intake's ``tenant`` (a line's own
        # ``metadata.tenant`` still wins, as it does live).
        use_columnar = decoder is None and self.recovery_decoder is None
        decoder = decoder or self.recovery_decoder or JsonLinesDecoder()
        start = reader.committed
        if from_offset is not None:
            start = min(start, max(0, int(from_offset)))
        # rows below the committed offset sealed before that offset
        # committed — replaying them must not duplicate persistence
        self.store_dedup_floor = max(self.store_dedup_floor,
                                     reader.committed)
        reader.seek(start)
        n = 0
        done = False
        while not done:
            records = reader.poll_records(max_records)
            if not records:
                break
            for offset, payload, tenant in records:
                if upto is not None and offset >= upto:
                    done = True
                    break
                if use_columnar:
                    fast = self._replay_columnar(payload, offset, tenant)
                    if fast is not None:
                        n += fast
                        continue
                try:
                    reqs = decoder(payload)
                except DecodeError as e:
                    self.ingest_failed_decode(payload, "journal-replay", e)
                    continue
                events = [r for r in reqs if r.event_type is not None]
                if not events:
                    continue
                tenants = [
                    self.resolve_tenant(r.metadata.get("tenant", tenant)
                                        if r.metadata else tenant)
                    for r in events
                ]
                self._run_plans(self._take(
                    lambda: self.batcher.add_requests(
                        events, tenants, [offset] * len(events))))
                n += len(events)
        if n:
            logger.info("replayed %d journaled events past offset %d",
                        n, reader.committed)
        self.flush()
        with self._lock:
            quiesced = (self._plans_outstanding == 0
                        and self.batcher.pending == 0
                        and not self._egress_busy)
        if quiesced:
            # every replayed sub-committed row has egressed; retire the
            # dedup mask so live egress stops paying for it (a timed-out
            # flush keeps the floor — correctness over the nanoseconds)
            self.store_dedup_floor = 0
        return n

    def _replay_columnar(self, payload: bytes, offset: int,
                         tenant: str = "default") -> Optional[int]:
        """Replay one journal record through the C columnar lane, or
        None when the STRICT measurement scanner doesn't accept it —
        the caller falls back to the scalar decoder.  Only the
        measurement scanner qualifies: it bails on ANY unknown request
        key, so a payload it accepts carries no ``metadata`` and every
        row's tenant is the record's, ``tenant`` — the same the scalar
        decoder's rows get, so the two replays produce bit-identical
        rows (the record's tenant, no alternate ids), and the same the
        live intake stamped (``_ingest_resolved_columns`` is shared).
        The family scanner is deliberately
        NOT used here — it skips unknown request keys, so it would
        accept a metadata-carrying payload and silently drop the
        per-request tenant the scalar replay honors.  Rows keep the
        original ``offset`` as payload_ref and the payload is NOT
        re-journaled."""
        from sitewhere_tpu.ingest.columnar import (
            _native_decode_resolved,
            space_of,
        )
        from sitewhere_tpu.ingest.decoders import DecodeError

        space = space_of(self.batcher.resolve_device)
        if space is None:
            return None
        # The scanner BAILS (None) on anything malformed or non-
        # measurement rather than raising — but its timestamp hardening
        # (_split_epoch) RAISES DecodeError for finite out-of-int32
        # eventDates, and a journal written by pre-hardening code may
        # hold exactly such a record.  Replay must never abort instance
        # boot over one bad record: fall through to the scalar decoder,
        # whose DecodeError handler owns dead-lettering.
        try:
            out = _native_decode_resolved(payload, space)
        except DecodeError:
            return None
        if out is None:
            return None
        columns, _host = out
        return self._ingest_resolved_columns(columns, offset, tenant)

    # -- one step -----------------------------------------------------------

    def _tables_packed(self):
        """PackedTables for the current provider epochs, identity-cached
        (re-packs only when a registry/rule/zone epoch actually changed).
        On a mesh the pack is placed with its canonical shardings
        (registry plane sharded by capacity, broadcast tables
        replicated) so steady-state steps reuse the resident buffers."""
        reg = self.registry_provider()
        rules = self.rules_provider()
        zones = self.zones_provider()
        c = self._tables_cache
        if c is not None and c[0] is reg and c[1] is rules and c[2] is zones:
            return c[3]
        t = self._pack_tables(reg, rules, zones)
        if self.mesh is not None:
            from sitewhere_tpu.pipeline.sharded import place_packed_tables

            t = place_packed_tables(self.mesh, t)
        self._tables_cache = (reg, rules, zones, t)
        return t

    def _run_plan(self, plan: BatchPlan, replay_depth: int = 0) -> None:
        """Route one emitted plan: full-width fill plans join the
        device-resident dispatch ring (chained K at a time); everything
        else — deadline/flush partials, re-injected plans, mesh
        plans — takes the single-step path, draining any ring-held
        predecessors first so per-device event order is preserved."""
        if self._ring_eligible(plan, replay_depth):
            self._stage_plan(plan)
            with self._step_lock:
                self._ring.append(plan)
                due = len(self._ring) >= self.ring_depth
            if due:
                wait = self._await_dispatch(plan.seq, stall=True)
                with self._step_lock:
                    wait.__exit__(None, None, None)
                    if len(self._ring) >= self.ring_depth:
                        self._run_ring()
            return
        if self.ring_depth and self._ring:
            # ordering barrier: rows already queued in the ring precede
            # this plan — step them first (stall only outside the egress
            # worker's own context, same rule as the single-step path).
            # Bounded by this plan's emission seq: concurrently appended
            # NEWER fill plans are successors, and draining them here
            # would both reorder them ahead of this plan and starve it
            # indefinitely under a sustained full-width stream.
            self._flush_ring(stall=replay_depth == 0,
                             upto_seq=plan.seq if plan.seq >= 0 else None)
        self._dispatch_plan(plan, replay_depth)

    def _ring_eligible(self, plan: BatchPlan, replay_depth: int) -> bool:
        """May this plan wait in the ring for a chained dispatch?  Only
        depth-0 full-width fill emissions:
        deadline/flush partials are latency-sensitive and re-injected
        plans (derived alerts, replay) must not recurse through the
        ring.  Mesh plans chain through the sharded packed chain — the
        fused mode — under the same eligibility rules.  The explicit
        width check matters with n_shards > 1, where a single skewed
        shard segment triggers a "fill" emission far below full width —
        those are latency-carrying partials too."""
        return (self.ring_depth > 0
                and replay_depth == 0
                and plan.reason == "fill"
                # a full pipeline.width: a deadline plan that fills its
                # narrow rung is still a latency-carrying partial
                and plan.n_events == plan.full_width
                # breaker demoted past CHAINED: bisectable single-step
                # dispatch only, until a cooldown probe succeeds
                and self.breaker.allow_chain())

    def _await_dispatch(self, seq: int, stall: bool):
        """Open the ``pipeline.stage_dispatch_wait_s`` span — the time
        the dispatch path is BLOCKED: a full egress window (``stall``)
        plus the wait for ``_step_lock`` — and stall.  The caller closes
        the returned span as its first statement under ``with
        self._step_lock`` (the span ends when the lock is held; the lock
        stays a ``with`` region for the lock-order lint).  One
        observation per wait entered, so the timer reads as a total."""
        wait = self._m_stage["dispatch_wait"].time(seq=seq)
        wait.__enter__()
        if stall:
            self._stall_for_egress_room()
        return wait

    def _stall_for_egress_room(self) -> None:
        """Bounded offload queue: stall — never while holding the step
        lock — once egress has fallen a full window behind."""
        if not self._offloaded():
            return
        deadline = time.monotonic() + 10.0
        while (len(self._inflight) >= self.egress_queue_depth
               and self._offloaded()
               and time.monotonic() < deadline):
            self._room_evt.clear()
            # re-check AFTER the clear: a slot freed between the
            # check above and the clear must not be lost to a full
            # poll interval
            if len(self._inflight) < self.egress_queue_depth:
                break
            self._room_evt.wait(0.05)
        else:
            if (self._offloaded()
                    and len(self._inflight) >= self.egress_queue_depth):
                # gave up on the stall bound: the window overfills
                # rather than deadlocking the producer, but an
                # operator must be able to see it happening
                self._m_stall_overflow.inc()
                logger.warning(
                    "egress stalled > 10s with %d plans in flight "
                    "(bound %d) — proceeding past the window bound",
                    len(self._inflight), self.egress_queue_depth)

    def _flush_ring(self, stall: bool = True,
                    upto_seq: Optional[int] = None) -> None:
        """Drain ring-held plans through the single-step path in emission
        order: the partial-ring deadline/flush path, and the ordering
        barrier ahead of a non-ring plan.  ``stall=False`` when called
        from the egress worker's own context (it must never block on its
        own backlog); ``upto_seq`` bounds the drain to plans emitted
        BEFORE that sequence number (the barrier's predecessors — newer
        arrivals stay ringed for their own chain).

        Each pop+dispatch happens under ONE step-lock hold: the ring is
        the ordered dispatch queue, so a concurrently refilled ring can
        never chain newer plans ahead of an older plan this drain has
        taken but not yet stepped (the stall, which must never run under
        the lock, sits between holds)."""
        while True:
            wait = self._await_dispatch(
                upto_seq if upto_seq is not None else -1, stall)
            with self._step_lock:
                wait.__exit__(None, None, None)
                if not self._ring:
                    return
                if upto_seq is not None and self._ring[0].seq >= upto_seq:
                    return
                plan = self._ring.pop(0)
                self._m_ring_flushes.inc()
                self._dispatch_plan(plan, 0, stall=False)

    def _flush_ring_if_due(self) -> None:
        """Loop-thread linger bound: a partial ring whose oldest plan has
        aged past the batcher deadline drains single-step, so the ring
        adds at most ~one deadline of latency under trickle traffic."""
        if not self.ring_depth:
            return
        with self._step_lock:
            due = bool(self._ring) and (
                time.monotonic() - self._ring[0].created_at
                >= self.batcher.deadline_s)
        if due:
            self._flush_ring()

    def _ring_chain(self, k: int):
        """The jitted K-step chain, built once per K (K is always
        ``ring_depth`` in steady state; the cache tolerates a mid-chaos
        variation without recompiling every dispatch)."""
        chain = self._ring_chains.get(k)
        if chain is None:
            if self.mesh is not None:
                from sitewhere_tpu.pipeline.sharded import (
                    build_sharded_packed_chain,
                )

                chain = build_sharded_packed_chain(
                    self.mesh, k, donate=self._ring_donate)
            else:
                from sitewhere_tpu.pipeline.packed import build_packed_chain

                chain = build_packed_chain(k, donate=self._ring_donate)
            self._ring_chains[k] = chain
        return chain

    @hot_path
    def _run_ring(self) -> None:
        """Dispatch one chained K-step program over the ring's staged
        slots (called under ``_step_lock`` with a full ring): one host
        dispatch covers K steps, the carry threads on device (donated —
        the state manager leased it exclusively), per-step output blocks
        come back stacked, and their D2H copies start immediately so the
        egress worker's ONE shared fetch per ring finds the bytes
        host-side.  Each slot then windows as its own plan: commits stay
        fail-closed per batch, attributed to the step that produced them."""
        # chaos hook: a chain-dispatch failure leaves every plan in the
        # ring — all stay outstanding, the commit gate fails closed, and
        # journal replay recovers their rows (at-least-once)
        faults.fire("dispatcher.step")
        from sitewhere_tpu.pipeline.packed import (
            RingFetch,
            RingStepView,
            start_host_copy,
        )

        plans = self._ring[:self.ring_depth]
        del self._ring[:self.ring_depth]
        k = len(plans)
        chain = self._ring_chain(k)
        now = time.monotonic()
        # per-shard containment (mesh): shards the breaker bank has
        # demoted get their rows side-routed + masked BEFORE the chain,
        # so one sick chip degrades its own shard without costing the
        # healthy shards the 1/K host-sync economy
        demoted = (self.breaker.demoted_shards()
                   if self._shard_breakers else ())
        if demoted:
            self._sidecar_shard_rows(plans, demoted)
        # ring-shaped scratch: slot references land in the preallocated
        # K-length lists (cleared after the dispatch), so the chain path
        # builds no per-dispatch lists (swlint HP001)
        slots_i, slots_f = self._ring_slots_i, self._ring_slots_f
        while len(slots_i) < k:   # mid-chaos partial chain (cold path)
            slots_i.append(None)
            slots_f.append(None)
        while len(slots_i) > k:
            slots_i.pop()
            slots_f.pop()
        for i, plan in enumerate(plans):
            self._m_stage["ring_wait"].observe(
                max(0.0, now - plan.created_at))
            staged = plan.staged or (plan.packed_i, plan.packed_f)
            slots_i[i] = staged[0]
            slots_f[i] = staged[1]
        failure = None
        with self._m_stage["ring_dispatch"].time(
                seq=plans[0].seq, steps=k) as span:
            tables = self._tables_packed()
            # one watchdog entry for the whole chain; each slot's egress
            # decrements a part, so the entry drains when the LAST slot
            # does (`plans` rides as the opaque payload — the trip
            # callback renders records lazily, off the per-batch hot path)
            wd = self.watchdog.begin(plans, parts=k)
            for plan in plans:
                self._wd_tokens[id(plan)] = wd
            ctrace = self.tracer.trace("pipeline.chain")
            try:
                if faults.device_active():
                    # device-fault injection point: fires against the
                    # HOST copies of the packed batch (plan.packed_i/f,
                    # always retained), so when_nonfinite matches exactly
                    # what the device would compute over
                    for plan in plans:
                        faults.device_fire("device.dispatch",
                                           values=plan.packed_f,
                                           valid=plan.packed_i[0] != 0)
                with ctrace.span("ring.dispatch").tag("steps", k):
                    _, ois, mets, _present = self._dispatch_chain(
                        chain, tables, slots_i, slots_f)
                start_host_copy(ois, mets,
                                on_error=self._on_host_copy_error)
            except Exception as e:
                failure = e
                span.discard()   # the timer counts dispatched chains
            finally:
                # drop the slot references: staged H2D buffers must not
                # outlive their ring pinned in the dispatch scratch
                for i in range(k):
                    slots_i[i] = None
                    slots_f[i] = None
            ctrace.end()
            if failure is None:
                # chaos kill point: the K-step chain dispatched and
                # committed on device, but NO slot has egressed — every
                # ring plan must replay
                faults.crosspoint("crash.mid_ring")
        if failure is not None:
            self._recover_ring(plans, failure)
            return
        chain_dt = span.elapsed
        self._m_ring_chains.inc()
        for plan in plans:
            plan.dispatch_s = chain_dt / k   # per-slot share of the chain
        fetch = RingFetch(ois, mets, on_fetch=self._m_host_syncs.inc,
                          wait_timer=self._m_device_wait,
                          seq=plans[0].seq)
        for slot, plan in enumerate(plans):
            trace = self.tracer.trace("pipeline.plan")
            trace.record("batch.assemble", plan.max_wait_s,
                         rows=plan.n_events, fill=round(plan.fill, 3))
            trace.record("ring.slot", max(0.0, now - plan.created_at),
                         slot=slot, seq=plan.seq, chain_k=k)
            self._m_assemble.observe(plan.max_wait_s)
            self._window_step(plan, RingStepView(fetch, slot), 0, trace)
        # a clean CHAINED dispatch closes a half-open breaker probe —
        # per-shard, it vouches only for the shards that actually rode
        # the chain (a masked shard proved nothing)
        if demoted:
            self.breaker.record_success(chained=True, masked=demoted)
        else:
            self.breaker.record_success(chained=True)

    def _recover_ring(self, plans, exc) -> None:
        """Chain-failure containment (runs under ``_step_lock``).

        The K plans were popped off the ring BEFORE the dispatch, so a
        raw failure would leave them invisible to every accounting
        surface that reads ``self._ring`` — ``oldest_unsealed_wait_s``
        (the overload ladder's queue-delay signal) and the partial-ring
        deadline drain both go blind.  Re-parking them at the FRONT
        restores that accounting (and emission order) first.

        The donated carry is not stranded either: the chain faulted, so
        ``commit_packed`` never ran and the state manager still holds
        the last committed epoch — each single-step re-dispatch below
        re-leases a fresh pack of it (``lease_generation`` advances on
        the same live manager: recovery without restart).  Recovery must
        NEVER touch the donated ``ps`` argument itself — swlint's DN001
        donation pass guards that statically.

        A re-dispatch that fails again is contained by
        :meth:`_contain_step_failure` (bisect → poison-row quarantine),
        and repeated faults across distinct batches trip the breaker.
        """
        self._ring[:0] = plans
        self._m_fault["chain_faults"].inc()
        if self._ring_donate:
            # the failed chain held the packed lease; the re-dispatches
            # below re-lease the carry from the last committed epoch
            self._m_fault["releases"].inc()
        for plan in plans:
            self._wd_end(plan)
        logger.warning(
            "chained dispatch failed (%d plans re-parked): %s",
            len(plans), exc)
        if self.flightrec is not None:
            for plan in plans:
                self._flight_record(
                    plan, None, 0, commit="device-fault",
                    error=f"{type(exc).__name__}: {exc}")
            self.flightrec.anomaly(
                "device-fault",
                detail=f"chain of {len(plans)} failed: "
                       f"{type(exc).__name__}: {exc}")
        # per-shard attribution on a mesh: nonfinite rows in a shard's
        # batch segment strike THAT shard's breaker; an unattributable
        # chain fault strikes every shard (fail conservative)
        self._record_device_fault(plans[0].seq, plans)
        # single-step re-dispatch in emission order; a plan that fails
        # AGAIN stays re-parked (front of the ring), keeps the commit
        # gate closed, and journal replay recovers it after restart
        for _ in range(len(plans)):
            plan = self._ring.pop(0)
            try:
                self._dispatch_plan(plan, 0, stall=False)
            except Exception:
                self._ring.insert(0, plan)
                logger.exception(
                    "single-step re-dispatch of seq=%d failed; "
                    "plan stays parked", plan.seq)
                break

    def _on_host_copy_error(self, exc) -> None:
        """A D2H output copy failed.  The dispatch itself committed, so
        the rows are NOT lost — but the egress fetch that follows will
        hit the same dead buffer.  Escalate beyond the counter: flag the
        plan's egress failure for a single-step re-dispatch (the state
        re-step is at-least-once, identical to journal replay) and dump
        the anomaly so the copy fault is attributable, not a mystery
        egress crash minutes later."""
        self._m_host_copy_err.inc()
        self._m_fault["host_copy_faults"].inc()
        self._copy_suspect = True
        logger.warning("device→host output copy failed: %s", exc)
        if self.flightrec is not None:
            self.flightrec.anomaly(
                "host-copy-fault",
                detail=f"{type(exc).__name__}: {exc}")

    # --- device-tier fault-containment callbacks (devguard wiring) ---

    def _on_breaker_trip(self, level: int) -> None:
        from sitewhere_tpu.runtime.devguard import BREAKER_LEVELS
        from sitewhere_tpu.runtime.overload import OverloadState

        self._m_fault["breaker_trips"].inc()
        self._m_breaker_state.set(level)
        logger.warning("device breaker tripped to %s",
                       BREAKER_LEVELS[level])
        if self.flightrec is not None:
            self.flightrec.anomaly(
                "device-breaker",
                detail=f"dispatch demoted to {BREAKER_LEVELS[level]}")
        if (self.overload is not None
                and self.overload.state == OverloadState.NORMAL):
            # ride the overload ladder: a demoted device tier sheds the
            # same way genuine pressure does, and the ladder's own
            # hysteresis owns any further escalation
            self.overload.force(OverloadState.DEGRADED,
                                reason="device-breaker")

    def _on_breaker_restore(self) -> None:
        from sitewhere_tpu.runtime.overload import OverloadState

        self._m_breaker_state.set(0)
        logger.info("device breaker restored chained dispatch")
        if (self.overload is not None
                and self.overload.state == OverloadState.DEGRADED
                and getattr(self.overload, "last_driver", None)
                == "device-breaker"):
            # release only our own demotion — a ladder driven by real
            # pressure meanwhile keeps its state
            self.overload.force(OverloadState.NORMAL,
                                reason="device-breaker-recovered")

    def _on_shard_breaker_trip(self, shard: int, level: int) -> None:
        """One mesh shard demoted (ShardBreakers callback): the gauge
        tracks the WORST shard, the flight recorder names the sick one,
        and the overload ladder only engages once NO shard can chain —
        a single demoted shard still rides masked on a healthy mesh."""
        from sitewhere_tpu.runtime.devguard import BREAKER_LEVELS
        from sitewhere_tpu.runtime.overload import OverloadState

        self._m_fault["breaker_trips"].inc()
        self._m_breaker_state.set(self.breaker.level)
        logger.warning("device breaker tripped to %s for mesh shard %d "
                       "(other shards keep chaining)",
                       BREAKER_LEVELS[level], shard)
        if self.flightrec is not None:
            self.flightrec.anomaly(
                "device-breaker",
                detail=f"shard {shard} demoted to {BREAKER_LEVELS[level]}")
        if (self.overload is not None
                and not self.breaker.allow_chain()
                and self.overload.state == OverloadState.NORMAL):
            self.overload.force(OverloadState.DEGRADED,
                                reason="device-breaker")

    def _on_shard_breaker_restore(self, shard: int) -> None:
        from sitewhere_tpu.runtime.overload import OverloadState

        self._m_breaker_state.set(self.breaker.level)
        logger.info("device breaker restored chained dispatch for "
                    "mesh shard %d", shard)
        if (self.breaker.level == 0
                and self.overload is not None
                and self.overload.state == OverloadState.DEGRADED
                and getattr(self.overload, "last_driver", None)
                == "device-breaker"):
            self.overload.force(OverloadState.NORMAL,
                                reason="device-breaker-recovered")

    def _fault_shards(self, plans) -> Optional[set]:
        """Attribute a mesh dispatch fault to shard(s): scan the retained
        HOST batch buffers for nonfinite float rows (the dominant device
        fault the injection harness and real poison produce) and map
        each poisoned row's batch position to its shard segment.  None =
        unattributable — the caller strikes every shard, because an
        un-guarded tier is worse than a conservatively demoted one."""
        if not self._mesh_shards:
            return None
        shards: set = set()
        for plan in plans:
            bf = np.asarray(plan.packed_f)
            valid = np.asarray(plan.packed_i[0]) != 0
            bad = valid & ~np.isfinite(bf).all(axis=0)
            for row in np.nonzero(bad)[0]:
                shards.add(int(row) // self._shard_seg)
        return shards or None

    def _record_device_fault(self, seq: int, plans) -> None:
        """Route one device fault into the breaker — per-shard when the
        bank is shard-aware AND the fault attributes to specific
        segments, tier-wide otherwise."""
        if not self._shard_breakers:
            self.breaker.record_fault(seq)
            return
        shards = self._fault_shards(plans)
        if shards is None:
            self.breaker.record_fault(seq)
        else:
            for s in sorted(shards):
                self.breaker.record_fault(seq, shard=s)

    def _sidecar_shard_rows(self, plans, demoted: tuple) -> None:
        """Demoted-shard side route (mesh ring, under ``_step_lock``):
        dispatch each ring plan's rows belonging to ``demoted`` shards
        through the containment subset path — the sharded single step
        while the shard sits at SINGLE_STEP, the CPU fallback once it
        reaches FALLBACK — then mask those rows out of the staged chain
        batch.  The healthy shards keep the fused chain; the sick
        shard's rows still flow (degraded), commit via the same
        read-epoch merge, and window/egress normally.  A side dispatch
        that FAILS leaves its rows in the chain on purpose: the chain
        fault that follows re-enters `_recover_ring`'s containment
        instead of silently dropping rows."""
        from sitewhere_tpu.runtime.devguard import FALLBACK

        fallback = any(self.breaker.level_of(s) >= FALLBACK
                       for s in demoted)
        step_fn = self._cpu_packed_step() if fallback else None
        if step_fn is None:
            # no addressable CPU device: demoted single-step through the
            # mesh beats a dead fallback (same policy as _dispatch_plan)
            fallback = False
        seg = self._shard_seg
        for plan in plans:
            valid = np.asarray(plan.packed_i[0]) != 0
            take = np.zeros(valid.shape[0], dtype=bool)
            for s in demoted:
                take[s * seg:(s + 1) * seg] = True
            rows = np.nonzero(take & valid)[0]
            if rows.size == 0:
                continue
            trace = self.tracer.trace("pipeline.shard-sidecar")
            trace.record("shard.sidecar", 0.0, seq=plan.seq,
                         rows=int(rows.size), shards=list(demoted))
            if not self._try_subset(plan, rows, 0, trace,
                                    step_fn=step_fn):
                logger.warning(
                    "sidecar dispatch for demoted shard(s) %s failed "
                    "(seq=%d); rows stay in the chain for containment",
                    demoted, plan.seq)
                continue
            if fallback:
                self._m_fault["cpu_fallback_steps"].inc()
            # mask the side-routed rows out of the chained dispatch:
            # fresh host buffer (the retained original must keep its
            # rows for bisect/dead-letter), restaged on the mesh
            bi = np.array(plan.packed_i, copy=True)
            bi[0][rows] = 0
            plan.packed_i = bi
            from sitewhere_tpu.pipeline.sharded import place_packed_batch

            plan.staged = place_packed_batch(self.mesh, bi, plan.packed_f)

    def _on_watchdog_soft(self, payload, elapsed_s: float) -> None:
        """Soft budget tripped: dump the in-flight dispatch's plan
        records to the flight recorder.  ``payload`` is the opaque
        value handed to ``watchdog.begin`` — a BatchPlan (single-step)
        or the ring's plan list (chained); records render HERE, on the
        cold trip path, never per batch."""
        plans = payload if isinstance(payload, list) else [payload]
        self._m_fault["watchdog_soft_trips"].inc()
        logger.warning("device dispatch slow: %.3fs in flight (budget "
                       "%.3fs), %d plan(s)", elapsed_s,
                       self.watchdog.soft_s, len(plans))
        if self.flightrec is not None:
            for i, plan in enumerate(plans):
                self.flightrec.record(
                    kind="hung-step",
                    **self._wd_record(plan,
                                      slot=i if len(plans) > 1 else None))
            self.flightrec.anomaly(
                "device-hung-step",
                detail=f"{elapsed_s:.3f}s in flight "
                       f"(soft budget {self.watchdog.soft_s:.3f}s)")

    def _on_watchdog_hard(self, payload, elapsed_s: float) -> None:
        self._m_fault["watchdog_hard_trips"].inc()
        # shard-scoped wedge attribution (mesh): the breaker bank's
        # suspects — shards with live strikes or an elevated level — are
        # the best available culprit for the wedge; () means the whole
        # tier is suspect and peers park everything, same as single-chip
        if self._shard_breakers:
            self._unhealthy_shards = self.breaker.suspect_shards()
        logger.error("device tier unhealthy: dispatch wedged %.3fs "
                     "(hard budget %.3fs)%s", elapsed_s,
                     self.watchdog.hard_s,
                     (f", suspect shards {self._unhealthy_shards}"
                      if self._unhealthy_shards else ""))
        if self.flightrec is not None:
            self.flightrec.anomaly(
                "device-wedged",
                detail=f"{elapsed_s:.3f}s in flight "
                       f"(hard budget {self.watchdog.hard_s:.3f}s)")

    def _on_watchdog_recovered(self) -> None:
        self._unhealthy_shards = ()
        logger.info("device tier recovered: in-flight dispatches drained")

    @property
    def device_unhealthy(self) -> bool:
        """Heartbeat export: True while the hung-step watchdog holds the
        tier unhealthy (rpc/forward.py carries it to peers)."""
        return self.watchdog.unhealthy

    @property
    def device_unhealthy_shards(self) -> tuple:
        """Heartbeat export, mesh refinement of :attr:`device_unhealthy`:
        the shard ids suspected in the CURRENT wedge.  Empty while
        healthy — and also when a wedge cannot be attributed, in which
        case peers treat the whole tier as sick (the conservative
        single-chip semantics)."""
        if not self.watchdog.unhealthy:
            return ()
        return self._unhealthy_shards

    def _wd_record(self, plan: BatchPlan, slot: Optional[int] = None) -> dict:
        rec = {"seq": int(plan.seq), "rows": int(plan.n_events),
               "reason": plan.reason}
        if slot is not None:
            rec["slot"] = slot
        return rec

    def _wd_end(self, plan: BatchPlan) -> None:
        self.watchdog.end(self._wd_tokens.pop(id(plan), None))

    def _on_egress_restart(self, exc) -> None:
        """Supervisor restart of the egress worker — a flight-recorder
        anomaly in its own right.  SAME reason as the worker's own
        crash dump on purpose: the rate limit is per reason, so the
        restart milliseconds after the crash coalesces into one
        snapshot instead of burning the retention budget twice."""
        if self.flightrec is not None:
            self.flightrec.anomaly(
                "egress-crash", detail=f"supervisor restart: {exc}")

    @hot_path
    def _flight_record(self, plan: BatchPlan, out, replay_depth: int,
                       commit: str, e2e_s: float = 0.0,
                       egress_s: float = 0.0, trace=None,
                       error: Optional[str] = None) -> None:
        """Append one structured per-batch record to the flight
        recorder: sequence, ring slot, per-host-stage timings, overload
        state, trace id, commit outcome — the black-box row an anomaly
        snapshot serializes.  Pure host dict work, no device access."""
        rec = {
            "seq": int(plan.seq),
            "reason": plan.reason,
            "rows": int(plan.n_events),
            "fill": round(plan.fill, 4),
            # the rung the plan stepped at (fill is of pipeline.width)
            "width": int(plan.width),
            "slot": getattr(out, "slot", None),
            "replay_depth": int(replay_depth),
            "wait_ms": round(plan.max_wait_s * 1e3, 3),
            "dispatch_ms": round(plan.dispatch_s * 1e3, 3),
            "egress_ms": round(egress_s * 1e3, 3),
            "e2e_ms": round(e2e_s * 1e3, 3),
            "overload": (self.overload.state.name
                         if self.overload is not None else "NORMAL"),
            "trace_id": getattr(trace, "trace_id", None),
            "commit": commit,
        }
        if error is not None:
            rec["error"] = error
        self.flightrec.record(**rec)

    @hot_path
    def _dispatch_plan(self, plan: BatchPlan, replay_depth: int = 0,
                       stall: bool = True) -> None:
        # chaos hook: a step-dispatch failure (device OOM, donation bug)
        # — the plan stays outstanding, so the commit gate fails closed
        faults.fire("dispatcher.step")
        self._stage_plan(plan)
        trace = self.tracer.trace("pipeline.plan")
        # the batcher wait of the oldest row = the "batch assemble" stage
        trace.record("batch.assemble", plan.max_wait_s,
                     rows=plan.n_events, fill=round(plan.fill, 3))
        self._m_assemble.observe(plan.max_wait_s)
        # Re-injected plans (depth > 0, which includes everything the
        # egress worker itself submits) skip the stall so the worker
        # can never block on its own backlog.
        wait = self._await_dispatch(plan.seq, stall and replay_depth == 0)
        with self._step_lock:
            wait.__exit__(None, None, None)
            failure = None
            with self._m_stage["dispatch"].time(seq=plan.seq) as span:
                try:
                    out = self._step_packed(plan, trace)
                except _StepFailed as e:
                    failure = e.__cause__
                    span.discard()   # the timer counts dispatched steps
            if failure is not None:
                self._wd_end(plan)
                self._contain_step_failure(plan, failure, replay_depth,
                                           trace)
                return
            plan.dispatch_s = span.elapsed   # flight-record attribution
            self._window_step(plan, out, replay_depth, trace)

    @hot_path
    def _step_packed(self, plan: BatchPlan, trace):
        """Launch the packed single step for ``plan`` and commit its
        state (under ``_step_lock``, inside the dispatch stage); returns
        the :class:`PackedView` over its outputs."""
        from sitewhere_tpu.pipeline.packed import start_host_copy

        tables = self._tables_packed()
        # staged pair (H2D already in flight) off the CPU backend; the
        # raw numpy buffers otherwise (the jitted call then transfers
        # synchronously)
        bi, bf = plan.staged or (plan.packed_i, plan.packed_f)
        if self.mesh is not None and plan.staged is None:
            from sitewhere_tpu.pipeline.sharded import place_packed_batch

            bi, bf = place_packed_batch(self.mesh, bi, bf)
        # breaker at FALLBACK: the chip is presumed dead — route the
        # same jitted program to a CPU device (single-chip path only; a
        # mesh program keeps its own placement)
        step_fn = self._packed_step
        if self.mesh is None:
            from sitewhere_tpu.runtime.devguard import FALLBACK

            if self.breaker.level >= FALLBACK:
                fallback = self._cpu_packed_step()
                if fallback is not None:
                    step_fn = fallback
                    self._m_fault["cpu_fallback_steps"].inc()
        wd = self.watchdog.begin(plan)
        self._wd_tokens[id(plan)] = wd
        try:
            if faults.device_active():
                # fires against the retained HOST copies, so the
                # injection point is mesh-agnostic — per-shard
                # containment drills rely on it firing here too
                faults.device_fire("device.dispatch",
                                   values=plan.packed_f,
                                   valid=plan.packed_i[0] != 0)
            with trace.span("step.dispatch").tag("rows", plan.n_events):
                _, oi, metrics, present = self._step_epoch(
                    step_fn, tables, bi, bf)
            # Start the egress fetches NOW, asynchronously: the copies
            # complete in the background while later plans step, so the
            # blocking np.asarray at the window's egress end finds the
            # bytes already on the host (≈0 RTT in steady state).
            start_host_copy(oi, metrics, on_error=self._on_host_copy_error)
        except Exception as e:
            raise _StepFailed() from e
        return self._packed_view(oi, metrics, present, plan.seq)

    def _step_epoch(self, step_fn, tables, bi, bf):
        """ONE single step on the state manager's epoch through its
        hand-off (:meth:`DeviceStateManager.step_packed`: read, dispatch
        and commit under its lock), laid out on the mesh first where
        there is one.  Returns the step's outputs; the carry it was
        handed is gone where ``step_fn`` donates it, so nothing here
        keeps it."""
        out = self.state_manager.step_packed(
            step_fn, tables, bi, bf, place=self._place_state)
        if self._ring_donate and step_fn is self._packed_step:
            self._m_steps_donated.inc()
        return out

    def _packed_view(self, oi, metrics, present, seq: int):
        """A step's :class:`PackedView`, its one blocking fetch counted
        (``pipeline.host_syncs``) and timed (``pipeline.device_wait_s``)."""
        from sitewhere_tpu.pipeline.packed import PackedView

        return PackedView(oi, metrics, present,
                          on_fetch=self._m_host_syncs.inc,
                          wait_timer=self._m_device_wait, seq=seq)

    def _contain_step_failure(self, plan: BatchPlan, exc,
                              replay_depth: int, trace) -> None:
        """A single-step packed dispatch failed: bisect the batch
        host-side until the poison rows are isolated (runs under
        ``_step_lock``).

        The full valid-row set is retried FIRST — a transient device
        fault recovers in one extra dispatch with zero loss.  A subset
        that still faults splits in half; singles that fault are poison
        and dead-letter replayably as ``device-poison`` (the raw
        columns ride the document, so ``requeue_dead_letter`` can
        re-ingest them after the producer is fixed).  Every CLEAN
        subset dispatches, commits, and windows normally — committed
        rows are never lost, only isolated poison rows leave the
        pipeline, and they leave with a paper trail.

        Subsets mask rows via ``valid=0`` columns (device semantics
        identical to a short batch), so disjoint subsets never double
        count and per-device writes keep their time-ordered winner
        scatter semantics regardless of subset order.
        """
        self._m_fault["step_faults"].inc()
        self._record_device_fault(plan.seq, (plan,))
        logger.warning("packed step failed for seq=%d (%d rows): %s — "
                       "bisecting", plan.seq, plan.n_events, exc)
        if self.flightrec is not None:
            self._flight_record(
                plan, None, replay_depth, commit="device-fault",
                trace=trace, error=f"{type(exc).__name__}: {exc}")
            self.flightrec.anomaly(
                "device-fault",
                detail=f"step seq={plan.seq} failed: "
                       f"{type(exc).__name__}: {exc}")
        try:
            valid_rows = np.nonzero(np.asarray(plan.packed_i[0]) != 0)[0]
            poison: List[int] = []
            stack = [valid_rows]
            while stack:
                rows = stack.pop()
                if rows.size == 0:
                    continue
                self._m_fault["bisect_rounds"].inc()
                if self._try_subset(plan, rows, replay_depth, trace):
                    continue
                if rows.size == 1:
                    poison.append(int(rows[0]))
                    continue
                mid = rows.size // 2
                stack.append(rows[mid:])
                stack.append(rows[:mid])
            if poison:
                self._m_fault["poison_rows"].inc(len(poison))
                logger.warning("isolated %d poison row(s) in seq=%d — "
                               "dead-lettering", len(poison), plan.seq)
                self._dead_letter_poison(plan, poison, exc)
        finally:
            # the original plan never egresses — its outstanding slot
            # (incremented at _take) retires here; clean subsets above
            # balanced their own increments through normal egress
            with self._lock:
                self._plans_outstanding -= 1

    def _try_subset(self, plan: BatchPlan, rows: np.ndarray,
                    replay_depth: int, trace, step_fn=None) -> bool:
        """Dispatch ``plan`` with only ``rows`` valid; True on success.

        Skips ``plan.staged`` on purpose: the bisect path rebuilds the
        batch from the retained HOST buffers (``packed_i``/``packed_f``)
        so the masked columns are exactly what the device sees.
        ``step_fn`` overrides the packed step — the demoted-shard
        sidecar routes FALLBACK-level shards through the CPU step."""
        bi = np.array(plan.packed_i, copy=True)
        mask = np.zeros(bi.shape[1], dtype=bool)
        mask[rows] = True
        bi[0] = np.where(mask, bi[0], 0)
        bf = plan.packed_f
        if step_fn is None:
            step_fn = self._packed_step
        try:
            if faults.device_active():
                faults.device_fire("device.dispatch", values=bf,
                                   valid=bi[0] != 0)
            tables = self._tables_packed()
            with self._lock:
                self._plans_outstanding += 1
            try:
                # each retry reads the epoch again through the hand-off,
                # never a carry an earlier attempt was handed
                new_ps, oi, metrics, present = self._step_epoch(
                    step_fn, tables, bi, bf)
                # surface async execution faults HERE, inside the
                # containment, not at the egress fetch
                jax.block_until_ready(new_ps)
            except Exception:
                with self._lock:
                    self._plans_outstanding -= 1
                raise
        except Exception:
            return False
        from sitewhere_tpu.pipeline.packed import start_host_copy

        start_host_copy(oi, metrics, on_error=self._on_host_copy_error)
        self._window_step(
            plan, self._packed_view(oi, metrics, present, plan.seq),
            replay_depth, trace)
        return True

    def _dead_letter_poison(self, plan: BatchPlan, rows: List[int],
                            exc) -> None:
        """Dead-letter isolated poison rows replayably: the document
        carries the raw host columns, so the ``device-poison`` requeue
        branch (instance.py) can rebuild and re-ingest the exact rows
        once the producer-side corruption is fixed."""
        if self.dead_letters is None:
            return
        idx = np.asarray(rows, dtype=np.int64)
        columns = {
            field: np.asarray(col)[idx].tolist()
            for field, col in plan.host_cols.items()
        }
        dead_letter(self.dead_letters, {
            "kind": "device-poison",
            "error": f"{type(exc).__name__}: {exc}",
            "seq": int(plan.seq),
            "count": len(rows),
            "columns": columns,
        }, metrics=self.metrics)
        if self.usage_ledger is not None and "tenant_id" in columns:
            self.usage_ledger.charge_rows_host(
                np.asarray(columns["tenant_id"], np.int64),
                "dead_letter_rows")

    def _cpu_packed_step(self):
        """Lazily build (and cache) the packed step jitted for a CPU
        device — the breaker's FALLBACK level.  Returns None when no CPU
        device is addressable (the caller then keeps the default path:
        demoted single-step beats a dead fallback)."""
        if self._cpu_step is False:
            return None
        if self._cpu_step is None:
            try:
                from sitewhere_tpu.pipeline.packed import build_packed_step

                cpu = jax.devices("cpu")[0]
                # not donating: its carry is a CPU copy of the device's
                jitted = build_packed_step(donate=False)

                def run(tables, ps, bi, bf, _cpu=cpu, _fn=jitted):
                    tables, ps, bi, bf = jax.device_put(
                        (tables, ps, bi, bf), _cpu)
                    return _fn(tables, ps, bi, bf)

                self._cpu_step = run
            except Exception as e:
                logger.warning("CPU fallback unavailable: %s", e)
                self._cpu_step = False
                return None
        return self._cpu_step

    def _offloaded(self) -> bool:
        """Is the supervised egress worker accepting work?  False before
        start(), after stop(), with ``egress_offload=False``, and once
        the worker has escalated terminally — every caller then falls
        back to the inline synchronous egress."""
        sup = self._egress_super
        return sup is not None and sup.alive and not sup.escalated

    @hot_path
    def _window_step(self, plan, out, replay_depth: int, trace) -> None:
        """Window the dispatched step in flight (dispatch is async).
        Offloaded: hand the window to the egress worker and return — the
        dispatch thread's step N+1 overlaps the worker's egress of N.
        Inline fallback: egress the oldest plans beyond the window on
        THIS thread while the device computes.  Called under _step_lock."""
        self.steps += 1
        self._m_steps.inc()
        if plan.width < plan.full_width:
            self._m_steps_narrow.inc()
        # the queue-entry stamp rides the item: inflight_wait is the
        # interval to its pop in _egress_guarded, across threads
        self._inflight.append((plan, out, replay_depth, trace,
                               time.perf_counter()))
        if self._offloaded():
            self._m_inflight.set(len(self._inflight))
            self._egress_evt.set()
            return
        while len(self._inflight) > self.inflight_depth:
            self._egress_guarded(self._inflight.popleft())

    def _drain_inflight(self, max_n: Optional[int] = None) -> None:
        if self._offloaded():
            # The worker owns draining: wake it and return.  Callers that
            # need COMPLETION gate on the accounting that already covers
            # offloaded egress — flush() waits for _plans_outstanding to
            # hit zero, the commit path re-checks _inflight next tick.
            self._egress_evt.set()
            return
        with self._step_lock:
            # Egress may re-inject (replay, derived alerts), which runs a
            # new step and appends it to the window — loop until settled
            # (bounded by max_replay_depth).
            n = 0
            while self._inflight and (max_n is None or n < max_n):
                self._egress_guarded(self._inflight.popleft())
                n += 1

    def _egress_worker(self) -> None:
        """Egress offload loop (runs under a Supervisor): pull dispatched
        steps off the window FIFO and fan them out, so the dispatch
        thread never blocks on a device→host fetch or a slow sink.  An
        egress exception propagates — the Supervisor counts the death,
        restarts the loop with backoff, and the failed plan stays
        outstanding (the commit gate fails closed; journal replay
        recovers its rows after a restart: at-least-once)."""
        while True:
            item = None
            with self._step_lock:
                if self._inflight:
                    item = self._inflight.popleft()
                    self._egress_busy = True
                elif self._egress_stop.is_set():
                    return
            if item is None:
                self._egress_evt.wait(0.01)
                self._egress_evt.clear()
                continue
            try:
                self._egress_guarded(item)
            finally:
                self._egress_busy = False
                self._room_evt.set()

    def _egress_guarded(self, item) -> None:
        """:meth:`_egress` with crash accounting — shared by the offload
        worker AND the inline fallback paths, so an egress failure is
        counted and flight-recorded (the crashed plan's record with its
        trace id, THEN the anomaly dump: the snapshot must contain the
        batch that died) no matter which thread ran it."""
        self._m_stage["inflight_wait"].observe(
            time.perf_counter() - item[4])
        # a bisected plan egresses once for each clean subset
        item[0].released = False
        try:
            try:
                self._egress(*item[:4])
            except Exception as e:
                self.egress_failures += 1
                self._m_egress_fail.inc()
                if self.flightrec is not None:
                    self._flight_record(
                        item[0], item[1], item[2], commit="failed",
                        trace=item[3],
                        error=f"{type(e).__name__}: {e}")
                    self.flightrec.anomaly("egress-crash", detail=str(e))
                plan = item[0]
                if self._copy_suspect and item[2] == 0:
                    # the async D2H copy for this window faulted
                    # (_on_host_copy_error flagged it); the egress fetch
                    # hit the dead buffer.  Re-dispatch the plan
                    # single-step — the state re-step is at-least-once,
                    # identical to journal replay.  Ring siblings that
                    # shared the dead fetch still fail closed and
                    # recover via replay: only the FIRST faulted plan
                    # retries inline.
                    self._copy_suspect = False
                    logger.warning(
                        "egress failed after host-copy fault; "
                        "re-dispatching seq=%d single-step", plan.seq)
                    self._dispatch_plan(plan, 1, stall=False)
                    return
                with self._lock:
                    # a raise after the release (the trace, metrics and
                    # flight-record tail) leaves no plan outstanding
                    # for flush to skip
                    if not plan.released:
                        self._plans_failed += 1
                raise
        finally:
            # watchdog retire happens whether egress succeeded, failed,
            # or handed off to a re-dispatch (the retry registers its
            # own entry); the pop is idempotent for bisected subsets
            self._wd_end(item[0])

    @hot_path
    def _egress(self, plan: BatchPlan, out, replay_depth: int,
                trace=None) -> None:
        """Host fan-out of one step's outputs.

        The input batch never leaves the host (``plan.host_cols``); only
        step outputs are fetched, and the rare-row masks (unregistered,
        derived alerts) only when their metric counters are nonzero.
        """
        from sitewhere_tpu.runtime.tracing import _NOOP_TRACE

        # chaos hook: an egress failure mid-window — the plan has already
        # stepped but never completes, so _plans_outstanding stays
        # elevated and the journal offset is NEVER committed past it
        # (at-least-once: a restart replays the record).  Offloaded, the
        # raise kills the egress WORKER mid-window; its supervisor
        # restarts the loop and the window's remaining plans still drain.
        faults.fire("dispatcher.egress")
        if trace is None:
            trace = _NOOP_TRACE
        with self._m_stage["egress"].time(seq=plan.seq) as span:
            lat = self._fan_out(plan, out, replay_depth, trace)
        if self.flightrec is not None:
            self._flight_record(plan, out, replay_depth, commit="ok",
                                e2e_s=lat, egress_s=span.elapsed,
                                trace=trace)

    @hot_path
    def _fan_out(self, plan: BatchPlan, out, replay_depth: int,
                 trace) -> float:
        """The egress stage's body (one ``pipeline.stage_egress_s``
        span): fetch the step's outputs — the one place the host blocks
        on the device, timed as ``pipeline.device_wait_s`` — then store,
        fan out, re-inject.  Returns the plan's end-to-end latency."""
        host_cols = plan.host_cols
        with trace.span("egress.fetch-outputs"):
            # the view counts and times its own lazy fetch (on_fetch /
            # wait_timer), which this access triggers; the accepted mask
            # is memoized on that same fetch
            m = as_numpy(out.metrics)
            accepted = out.accepted
            cols = self._columns(host_cols, out)
        for key in ("processed", "accepted", "unregistered", "unassigned",
                    "threshold_alerts", "zone_alerts"):
            count = int(getattr(m, key))
            self.totals[key] += count
            if count:
                self._m_totals[key].inc(count)
        # On-device occupancy telemetry: the views expose the
        # TELEMETRY_SCALARS block from the SAME fetched metrics vector
        # (zero additional syncs).
        self._m_occ["rows_admitted"].set(int(m.processed))
        self._m_occ["rules_fired"].set(
            int(m.threshold_alerts) + int(m.zone_alerts))
        # genuinely lost rows: the device counter is width - valid,
        # which on a partial plan mostly counts batch PADDING — the
        # plan's real row count is host knowledge, so subtract here
        self._m_occ["rows_invalid"].set(
            max(0, int(plan.n_events) - int(m.processed)))
        telemetry = out.telemetry
        if telemetry:
            for key in ("state_writes", "presence_merges"):
                if key in telemetry:
                    self._m_occ[key].set(telemetry[key])
            # Numeric-integrity quarantine: the device counted this
            # plan's NaN/Inf rows on the SAME packed metrics vector
            # (zero extra syncs) — the per-device host attribution scan
            # below runs only on the rare nonzero path.
            nf = int(telemetry.get("rows_nonfinite", 0))
            if nf:
                self._m_quar_rows.inc(nf)
                self._scan_quarantine(plan, replay_depth)
        # Tenant metering: fold the device-side per-tenant scatter block
        # (same fetched vector — zero extra syncs) into the usage ledger
        if self.usage_ledger is not None:
            with self._m_stage["meter"].time(seq=plan.seq):
                self._meter_plan(out, host_cols)
        # monotonic receive time of the plan's oldest row — the watermark
        # the per-stage ingest→seal / ingest→ack gauges measure from
        ingest_t0 = plan.created_at - plan.max_wait_s

        refs = host_cols["payload_ref"]
        journaled = refs != NULL_ID
        if journaled.any():
            self._max_egressed_ref = max(
                self._max_egressed_ref, int(refs[journaled].max()))

        # 1. persistence (event-management analog).  Replay below the
        # committed offset (checkpoint-restore floor) skips rows already
        # durably stored — their state/analytics effects still re-run.
        store_mask = accepted
        if self.store_dedup_floor > 0:
            store_mask = accepted & ((refs == NULL_ID)
                                     | (refs >= self.store_dedup_floor))
        if self.event_store is not None and store_mask.any():
            with trace.span("egress.persist").tag(
                    "rows", int(store_mask.sum())):
                self.event_store.append_columns(cols, mask=store_mask)
            self._m_seal.set(time.monotonic() - ingest_t0)
        elif accepted.any() and (self.outbound is not None
                                 or self.analytics is not None):
            # the store path would have fetched the enrichment columns
            # (releasing the step output); without it, fetch-and-release
            # here so async outbound/analytics queues holding the view
            # never pin this step's device buffers.  With no async
            # consumer at all, the view dies with this frame and the
            # device sync is genuinely skipped.
            release = getattr(cols, "release_output", None)
            if release is not None:
                release()
        # chaos kill point: stored (possibly sealed) but the offset
        # commit below never runs — a restart must replay this plan
        faults.crosspoint("crash.mid_egress")

        # 2. enriched fan-out (outbound connectors + rule processor hosts)
        #    — the trace rides along so the async delivery span joins it
        if self.outbound is not None and accepted.any():
            with trace.span("egress.outbound"):
                self.outbound.submit(cols, accepted, trace=trace,
                                     ingest_t0=ingest_t0)

        # 2b. streaming analytics: live window/CEP query evaluation
        #     (non-blocking offer; sheds itself from SHEDDING up as a
        #     non-priority consumer — see QueryRunner.submit_live)
        if self.analytics is not None and accepted.any():
            with trace.span("egress.analytics"):
                # the committed offset rides along as the runner's
                # fully-applied watermark: queue order guarantees every
                # batch carrying rows of records below it was offered
                # (and thus evaluates) before this one
                self.analytics.submit_live(
                    cols, accepted, trace=trace,
                    committed=(int(self.journal_reader.committed)
                               if self.journal_reader is not None
                               else None))

        # 2c. tenant rule programs (rules/engine.RuleEngineRunner):
        #     compiled per-structure kernels over the same accepted
        #     enriched batch; fired programs come back through
        #     inject_rule_alerts as first-class ALERT events
        if self.rules_engine is not None and accepted.any():
            with trace.span("egress.rules"):
                self.rules_engine.submit_live(
                    cols, accepted, trace=trace,
                    committed=(int(self.journal_reader.committed)
                               if self.journal_reader is not None
                               else None))

        # 3. command invocations (command-delivery analog)
        cmd_mask = accepted & (cols["event_type"] == EventType.COMMAND_INVOCATION)
        if self.on_command_rows is not None and cmd_mask.any():
            self.totals["commands"] += int(cmd_mask.sum())
            with trace.span("egress.commands"):
                self.on_command_rows(cols, cmd_mask, trace=trace)

        # 4. auto-registration + replay (device-registration analog)
        if int(m.unregistered) > 0:
            with trace.span("egress.registration"):
                self._handle_unregistered(host_cols, out, replay_depth)

        # 5. derived alerts re-injection (rule outputs become first-class
        #    events, reference ZoneTestRuleProcessor fires alerts back
        #    through event management) — fetched only when rules fired
        if int(m.threshold_alerts) + int(m.zone_alerts) > 0:
            with trace.span("egress.derived-alerts"):
                self._reinject_derived(plan, out, replay_depth)

        # Egress complete: record the plan's end-to-end latency (batcher
        # wait of its oldest row + emit→egress) and release it from the
        # commit gate.  On an exception above the count stays elevated —
        # commits stop (fail closed) rather than risk committing past an
        # un-egressed record.  The deque append shares _lock with
        # metrics_snapshot's copy (deques error on mutation-mid-iteration).
        lat = max(0.0, time.monotonic() - plan.created_at) + plan.max_wait_s
        with self._lock:
            self.latencies_s.append(lat)
            self._plans_outstanding -= 1
            plan.released = True
        # Close the trace: for tail candidates this IS the retention
        # decision (errored/slow traces flip to sampled, so the async
        # outbound/command spans still land in the ring).  The e2e
        # histogram exemplar uses the post-decision sampled flag — only
        # traces an operator can actually open are linked.
        trace.end()
        self._m_e2e.observe(
            lat, trace_id=(trace.trace_id if trace.sampled else None))
        self._m_queue.set(self.batcher.pending)
        self._m_inflight.set(len(self._inflight))
        return lat

    def _columns(self, host_cols: Dict[str, np.ndarray], out):
        """Egress columns as a zero-copy view (see :class:`EgressColumns`)
        — no per-batch dict build, no eager enrichment fetches (the
        retired ROADMAP-2 worklist entry: the 4.0 ms dispatch-bookkeeping
        suspect)."""
        return EgressColumns(host_cols, out)

    def _meter_plan(self, out, host_cols: Dict[str, np.ndarray]) -> None:
        """Bill one egressed plan to its tenants (tenant metering plane).

        The device already bucketed accepted rows / state writes /
        nonfinite rows by ``tenant_id % TENANT_METER_SLOTS`` inside the
        compiled step; the ledger resolves buckets against the plan's
        retained host tenant column (exact attribution, collision-
        apportioned) — no per-row host work on the common path.  The
        decode stage's running-total delta rides along so decode time
        is row-share-attributed to the same tenants."""
        block = out.tenant_meter
        tenants = host_cols.get("tenant_id") if host_cols else None
        if block is None or tenants is None:
            return
        decode_total = self._m_stage["decode"].total
        decode_s = max(0.0, decode_total - self._meter_decode_mark)
        self._meter_decode_mark = decode_total
        try:
            self.usage_ledger.charge_device_block(
                block, tenants, decode_s=decode_s)
            self.usage_ledger.publish(min_interval_s=1.0)
        except Exception:
            logger.exception("tenant metering failed for one plan")

    def _scan_quarantine(self, plan: BatchPlan, replay_depth: int) -> None:
        """Per-device attribution of the plan's nonfinite rows (called
        ONLY when the device-counted ``rows_nonfinite`` telemetry scalar
        is nonzero — never on the clean path).

        The device already masked these rows out of state, rules, and
        analytics (pipeline/step.py) and counted them per device in
        ``DeviceState.nonfinite_count``; this host scan re-derives the
        row set from the RETAINED numpy columns to accumulate a
        per-device strike count.  A device crossing
        ``quarantine_after`` cumulative poison rows emits ONE
        STATE_CHANGE (``STATE_CHANGE_QUARANTINED``) through the normal
        re-injection egress — downstream consumers see the quarantine
        exactly like a presence transition."""
        host = plan.host_cols
        if not host or "device_id" not in host:
            return
        valid = np.asarray(host["valid"]) != 0 if "valid" in host \
            else np.asarray(plan.packed_i[0]) != 0
        finite = np.ones(valid.shape, dtype=bool)
        for field in ("value", "lat", "lon", "elevation"):
            col = host.get(field)
            if col is not None:
                finite &= np.isfinite(np.asarray(col, dtype=np.float32))
        bad = valid & ~finite
        if not bad.any():
            return
        devs = np.asarray(host["device_id"])[bad].tolist()
        tens = (np.asarray(host["tenant_id"])[bad].tolist()
                if "tenant_id" in host else [0] * len(devs))
        newly = []
        for dev, ten in zip(devs, tens):
            if dev < 0:
                continue
            seen = self._nonfinite_seen.get(dev, 0) + 1
            self._nonfinite_seen[dev] = seen
            if (seen >= self.quarantine_after
                    and dev not in self._quarantined):
                self._quarantined.add(dev)
                newly.append((int(dev), int(ten)))
        self._m_quar_devices.set(len(self._quarantined))
        if not newly:
            return
        self._m_quar_changes.inc(len(newly))
        logger.warning("quarantined %d device(s) for nonfinite values: %s",
                       len(newly), [d for d, _ in newly])
        if self.flightrec is not None:
            # ring record BEFORE the anomaly dump so the snapshot's own
            # evidence includes which devices tripped and on which plan
            # (tools/flightrec_timeline.py renders kind-style records)
            self.flightrec.record(
                kind="quarantine", seq=int(plan.seq),
                rows=len(devs), devices=[d for d, _ in newly],
                strikes=self.quarantine_after)
            self.flightrec.anomaly(
                "device-quarantine",
                detail=f"devices {[d for d, _ in newly]} crossed "
                       f"{self.quarantine_after} nonfinite rows")
        if replay_depth < self.max_replay_depth:
            from sitewhere_tpu.state.presence import (
                STATE_CHANGE_QUARANTINED,
                state_change_columns,
            )

            cols = state_change_columns(
                np.asarray([d for d, _ in newly], np.int32),
                np.asarray([t for _, t in newly], np.int32),
                int(time.time()), code=STATE_CHANGE_QUARANTINED)
            self._run_plans(self._take(
                lambda: self.batcher.add_arrays(_copy=False, **cols)),
                replay_depth + 1)

    def _handle_unregistered(self, host_cols, out, replay_depth: int) -> None:
        """Rows the step refused as ``unregistered``: the default
        tenant's go to the registration manager (auto-register, replay;
        what it turns down is dropped with its warning, as before); what
        cannot replay, and every row that came in for a tenant other
        than ``default``, dead-letters."""
        mask = np.asarray(out.unregistered)
        if not mask.any():
            return
        # The registration manager registers into the DEFAULT tenant's
        # device management.  A row that came in for another tenant (its
        # source's, or its line's metadata) is never its business: the
        # device is unknown to that tenant or another tenant's, and
        # either way the row is refused for good — dead-lettered, never
        # re-registered under tenant 0 and replayed there.
        foreign = mask & (np.asarray(host_cols["tenant_id"])
                          != self.resolve_tenant("default"))
        if foreign.any():
            if self.dead_letters is not None:
                dead_letter(self.dead_letters, {
                    "kind": "unregistered", "count": int(foreign.sum()),
                    "tenant_ids": np.unique(
                        host_cols["tenant_id"][foreign]).tolist(),
                    "refs": [int(r) for r in np.unique(
                        host_cols["payload_ref"][foreign])
                        if int(r) != NULL_ID]})
            mask = mask & ~foreign
            if not mask.any():
                return
        refs = host_cols["payload_ref"][mask]
        requests: List[DecodedRequest] = []
        unreplayable: List[int] = []
        if self.journal is not None and self.registration is not None:
            # resolve original requests from the journal for replay;
            # rows from one multi-event payload share an offset, so decode
            # each distinct ref once
            from sitewhere_tpu.ingest.decoders import JsonLinesDecoder

            decoder = JsonLinesDecoder()  # handles envelopes AND NDJSON
            unreplayable = [int(r) for r in refs if int(r) == NULL_ID]
            for ref in dict.fromkeys(int(r) for r in refs if int(r) != NULL_ID):
                try:
                    # host-plane lines (registrations, stream data) were
                    # handled at first ingest; only events replay — a
                    # host-plane request would wedge the batcher
                    requests.extend(
                        r for r in decoder(self.journal.read_one(ref))
                        if r.event_type is not None)
                except Exception:
                    logger.debug("unreplayable payload ref %d", ref)
                    unreplayable.append(ref)
        else:
            unreplayable = [int(r) for r in refs]
        # every unreplayable row dead-letters, even when siblings replay
        if unreplayable and self.dead_letters is not None:
            dead_letter(self.dead_letters,
                        {"kind": "unregistered", "count": len(unreplayable),
                         "refs": unreplayable})
        if self.registration is None or not requests:
            return
        # A multi-event payload shares one journal ref across rows, so the
        # re-decode above returns EVERY event in the payload — drop only
        # the siblings THIS plan processed normally (their dense id
        # appears on a non-unregistered row of the same payload).  A
        # token that raced to registration between intake and egress
        # resolves to an id outside this plan's processed set and is
        # still replayed — filtering must never lose an event.
        replayed_refs = np.isin(
            host_cols["payload_ref"],
            [int(r) for r in dict.fromkeys(int(r) for r in refs)
             if int(r) != NULL_ID])
        sibling_processed = {
            int(i)
            for i in host_cols["device_id"][replayed_refs & ~mask]
            if int(i) != NULL_ID
        }
        if sibling_processed:
            requests = [
                r for r in requests
                if self.batcher.resolve_device(r.device_token)
                not in sibling_processed
            ]
        if not requests:
            return
        replay = self.registration.process_unregistered(requests)
        if replay and replay_depth < self.max_replay_depth:
            self.totals["replayed"] += len(replay)

            def intake():
                out = []
                for req in replay:
                    tenant_id = self.resolve_tenant(
                        req.metadata.get("tenant", "default")
                        if req.metadata else "default"
                    )
                    plan = self.batcher.add(req, tenant_id=tenant_id,
                                            payload_ref=NULL_ID)
                    if plan is not None:
                        out.append(plan)
                return out

            self._run_plans(self._take(intake), replay_depth + 1)

    def _reinject_derived(self, plan: BatchPlan, out,
                          replay_depth: int) -> None:
        if replay_depth >= self.max_replay_depth:
            return
        # Reconstruct the (rare) derived rows from host columns + the
        # packed output block — no same-width EventBatch round-trip off
        # the device.
        rows = np.nonzero(out.derived_valid)[0]
        if rows.size == 0:
            return
        self.totals["derived_alerts"] += int(rows.size)
        cols = out.derived_cols(plan.host_cols, rows)
        self._run_plans(self._take(
            lambda: self.batcher.add_arrays(_copy=False, **cols)),
            replay_depth + 1)

    def inject_rule_alerts(self, cols: Dict[str, np.ndarray]) -> int:
        """Re-inject fired tenant-program alerts as first-class ALERT
        events (the BYO-rules half of the derived-alert contract).

        Called from the rule engine's worker thread — the dispatcher
        lock is an RLock and ``_take``/``_run_plans`` serialize against
        live intake, so the injection is just another intake edge.  The
        engine builds the columns with ``update_state=False`` (derived
        alerts never re-fold trailing state) and the kernels mask ALERT
        rows at eval, so the path cannot self-amplify."""
        n = int(np.asarray(cols["device_id"]).size)
        if n == 0:
            return 0
        self.totals["derived_alerts"] += n
        self.totals["rule_program_alerts"] = (
            self.totals.get("rule_program_alerts", 0) + n)
        self._run_plans(self._take(
            lambda: self.batcher.add_arrays(_copy=False, **cols)))
        return n

    def requeue_rows(self, cols: Dict[str, np.ndarray]) -> int:
        """Re-ingest raw event columns through the normal batch path —
        the ``device-poison`` dead-letter requeue (instance.py): the
        isolated rows re-enter exactly like fresh ingest once the
        producer-side corruption is fixed.  Returns the row count."""
        n = int(np.asarray(cols["device_id"]).size)
        if n == 0:
            return 0
        self._run_plans(self._take(
            lambda: self.batcher.add_arrays(_copy=False, **cols)))
        return n

    def oldest_unsealed_wait_s(self) -> float:
        """LIVE ingest→seal watermark: age of the oldest event admitted
        but not yet through egress — the overload controller's lag
        signal.  The last-value seal gauge can't serve here: one slow
        plan (a jit compile) pins it at a historical spike for as long
        as anything is busy, reading as sustained overload when the
        system is actually healthy.  This measure self-decays: work
        seals, the wait disappears.  Lock-free reads (a torn read only
        skews one sample)."""
        if self.steps == 0:
            # warm-up gate: before the FIRST step completes, rows wait
            # on the jit compile (seconds), which is boot cost — not
            # overload.  Compiles are shape-cached after this; the
            # other signals (backlog fractions) still guard a wedged
            # boot.
            return 0.0
        now = time.monotonic()
        wait = 0.0
        oldest = self.batcher._oldest
        if oldest is not None and self.batcher.pending > 0:
            wait = now - oldest
        try:
            plan = self._inflight[0][0]
            wait = max(wait, now - plan.created_at + plan.max_wait_s)
        except IndexError:
            pass
        # Ring-held plans are in flight too (emitted, not yet stepped):
        # with multiple steps buffered for a chained dispatch, the
        # overload signal must reflect the OLDEST of them, not only the
        # already-windowed steps — otherwise a wedged ring reads healthy.
        try:
            plan = self._ring[0]
            wait = max(wait, now - plan.created_at + plan.max_wait_s)
        except IndexError:
            pass
        return max(0.0, wait)

    def metrics_snapshot(self) -> Dict[str, object]:
        with self._lock:
            pending = self.batcher.pending
            samples = list(self.latencies_s)
        snap: Dict[str, object] = {
            "steps": self.steps,
            "pending_rows": pending,
            # device-resident dispatch loop surface: how often the host
            # touched the device, and how much of the traffic rode chains
            "host_syncs": int(self._m_host_syncs.value),
            "ring_depth": self.ring_depth,
            "ring_chains": int(self._m_ring_chains.value),
            "ring_flushed_plans": int(self._m_ring_flushes.value),
            "device_fault": {
                "breaker": self.breaker.snapshot(),
                "watchdog": self.watchdog.snapshot(),
                "quarantined_devices": len(self._quarantined),
            },
            **self.totals,
        }
        if samples:
            lat = np.asarray(samples)
            snap["latency_p50_ms"] = round(float(np.percentile(lat, 50)) * 1e3, 3)
            snap["latency_p99_ms"] = round(float(np.percentile(lat, 99)) * 1e3, 3)
        return snap

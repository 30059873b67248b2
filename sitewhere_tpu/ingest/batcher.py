"""Deadline-driven batcher: decoded requests → routed fixed-shape batches.

This is the seam between the variable-rate host world and the static-shape
SPMD pipeline (SURVEY.md §7 hard part #1).  The reference's analog is the
Kafka producer partitioner + consumer poll batching
(``EventSourcesManager.java:166``, ``MicroserviceKafkaConsumer.java:123-128``):
events keyed by device token land in per-partition record batches.  Here:

- intake is COLUMNAR: rows live in per-shard queues of numpy column
  chunks, written once at intake (vectorized ``add_arrays`` gathers one
  slice per field per shard; the scalar ``add`` paths append into a
  growable staging chunk) and copied exactly once more at emission, by
  slice, into the fixed-shape batch — no per-row per-field Python loops
  anywhere on the hot path;
- each event row is routed to the mesh shard that owns its device registry
  block (:func:`~sitewhere_tpu.parallel.mesh.shard_for_device`), preserving
  the shard-local-gather invariant of the sharded pipeline step;
- a batch is emitted when any shard segment fills (``width // n_shards``
  rows) or when the oldest pending event exceeds the deadline — bounding
  added latency the way the Mongo buffer bounds flush delay
  (``DeviceEventBuffer.java:40-46``, ≤250 ms there; default 5 ms here for
  the <10 ms p99 budget) — or, for a live wire payload that finds the
  pipeline empty (nothing pending, no plan outstanding), at once
  (:meth:`Batcher.emit_idle`): the deadline is paid only while there is
  something to coalesce behind;
- rows that don't fit carry over to the next batch (no drops);
- unknown devices round-robin across shards and dead-letter on-device.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Deque, Dict, List, Optional, Sequence

import numpy as np

from sitewhere_tpu.analysis.markers import hot_path
from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.ingest.decoders import DecodedRequest
from sitewhere_tpu.parallel.mesh import shard_for_device
from sitewhere_tpu.schema import EventBatch

_FIELDS = (
    ("valid", np.bool_, False),
    ("device_id", np.int32, NULL_ID),
    ("tenant_id", np.int32, NULL_ID),
    ("event_type", np.int32, 0),
    ("ts_s", np.int32, 0),
    ("ts_ns", np.int32, 0),
    ("mtype_id", np.int32, NULL_ID),
    ("value", np.float32, 0.0),
    ("lat", np.float32, 0.0),
    ("lon", np.float32, 0.0),
    ("elevation", np.float32, 0.0),
    ("alert_code", np.int32, NULL_ID),
    ("alert_level", np.int32, 0),
    ("command_id", np.int32, NULL_ID),
    ("payload_ref", np.int32, NULL_ID),
    ("update_state", np.bool_, True),
)

# Data columns (everything but the emission-owned `valid` flag).
_COL_FIELDS = tuple(name for name, _, _ in _FIELDS[1:])
_DTYPE = {name: dt for name, dt, _ in _FIELDS}
_FILL = {name: fill for name, _, fill in _FIELDS}
# 0-d fill templates: `np.broadcast_to(_FILL_0D[f], n)` is a zero-copy
# 0-stride view of any length — intake never allocates a full column for
# an omitted field again (emission copies by slice regardless).  The
# views are read-only; nothing on the intake/emit path writes into a
# queued chunk's columns, only the freshly-allocated batch buffers.
_FILL_0D = {name: np.full((), fill, dt) for name, dt, fill in _FIELDS}
# Bytes one emitted row occupies across every batch column (the unit of
# the pipeline.bytes_copied.batch accounting).
_ROW_BYTES = sum(np.dtype(dt).itemsize for _, dt, _ in _FIELDS)

# The narrowest rung of the width ladder (:func:`plan_rungs`).  It bounds
# the compiles at boot for small widths: a batcher of 128 columns or
# fewer keeps one program.  One vector tile of the chip (128 lanes, the
# axis a plan's rows lie along) was the number at hand; no chip reading
# stands behind it, and no shipped width meets it (65,536 -> 1,024,
# 16,384 -> 256).
_MIN_RUNG = 128


def plan_rungs(width: int) -> tuple:
    """The widths a single-shard batcher emits plans at, ascending, at
    most four and always ending in ``width``: ``width / 64``, ``/ 16``,
    ``/ 4`` and ``width`` itself, none under :data:`_MIN_RUNG`.  A
    deadline, idle or flush emission takes the smallest rung that holds its
    rows, so a 1,024-row payload steps a 1,024-column program and not
    the ``pipeline.width`` one (PERF.md §6, PR 33).  A fixed function of
    the configured width: the dispatcher compiles one step a rung at
    ``start()``, and nothing else chooses."""
    return tuple(sorted({min(width, max(_MIN_RUNG, width >> shift))
                         for shift in (6, 4, 2, 0)}))


# Packed wire layout (pipeline/packed.py BATCH_I/BATCH_F), cached on
# first use — reservations allocate their columns AS rows of a packed
# buffer pair so a full-width reserved segment is H2D-ready as-is.
_PACKED_LAYOUT = None


def _packed_layout():
    global _PACKED_LAYOUT
    if _PACKED_LAYOUT is None:
        from sitewhere_tpu.pipeline.packed import BATCH_F, BATCH_I

        _PACKED_LAYOUT = (BATCH_I, BATCH_F,
                          {f: i for i, f in enumerate(BATCH_I)},
                          {f: i for i, f in enumerate(BATCH_F)})
    return _PACKED_LAYOUT


@dataclasses.dataclass
class _Chunk:
    """A columnar run of pending rows on one shard.

    ``start`` = rows already emitted; ``length`` = rows written.  A chunk
    whose backing arrays are longer than ``length`` is a *staging* chunk —
    the scalar add paths append into it in place (amortizing allocation);
    vectorized chunks arrive full (``length == capacity``).  A chunk
    carrying a ``reserved`` back-reference was filled in place by the
    fill-direct wire scanner (:meth:`Batcher.reserve`); when such a chunk
    is the sole content of a full-width packed emission, ``_emit`` adopts
    its buffers as the batch outright instead of copying.
    """

    cols: Dict[str, np.ndarray]
    length: int
    arrival: float
    start: int = 0
    reserved: Optional["Reservation"] = None
    # Row offset of this chunk inside its reservation's buffers (sharded
    # commits enqueue per-shard VIEWS of one buffer; adoption needs each
    # view to sit exactly at its shard's segment).
    res_off: int = 0

    @property
    def capacity(self) -> int:
        return len(self.cols["device_id"])


class Reservation:
    """A writable, packed-layout column segment for the fill-direct scan.

    :meth:`Batcher.reserve` hands the native wire scanner
    (``decode_measurement_lines_resolved_into``) direct int32/float32
    views into a fresh packed buffer pair — the same ``[C, B]`` rows the
    emitted batch ships H2D — so the hot path is recv → C scan+validate →
    in-place columnar write → H2D stage with zero intermediate copies.

    Contract:

    - the buffers are PRIVATE to this reservation until :meth:`commit`
      enqueues them under the dispatcher's intake lock, so concurrent
      decode workers can fill reservations in parallel and commit in
      delivery order — and a mid-payload bail simply never commits,
      leaving no torn rows by construction (:meth:`abort` just drops it);
    - the scanner writes ``device_id``, ``mtype_id`` (via the
      ``name_idx`` scratch + one remap), ``value``, ``ts_s``, ``ts_ns``
      and ``update_state``; every other column is a 0-stride fill
      template (PR 3's layout) or a per-payload constant
      (:meth:`set_const`) — nothing is materialized per row;
    - a full-width reservation that is the sole pending content when the
      batch emits is ADOPTED: its buffers become the packed plan and the
      batch-assembly copy disappears entirely.  Adopted ``host_cols``
      expose ``valid``/``update_state`` as int32 rows (not bool) — no
      egress consumer reads them, only the device does.
    """

    __slots__ = ("_batcher", "ibuf", "fbuf", "name_idx", "cap", "n",
                 "tenant_id", "payload_ref", "_open")

    def __init__(self, batcher: "Batcher", cap: int):
        _, _, bi, bf = _packed_layout()
        self._batcher = batcher
        self.cap = cap
        self.n = 0
        self.tenant_id = 0
        self.payload_ref = NULL_ID
        self._open = True
        self.ibuf = np.empty((len(bi), cap), np.int32)
        self.fbuf = np.empty((len(bf), cap), np.float32)
        self.name_idx = np.empty(cap, np.int32)
        if cap == batcher.width:
            # adoption candidate: pre-fill the columns the scanner never
            # writes (off the intake lock — commit stays O(1))
            for f in ("event_type", "alert_code", "alert_level",
                      "command_id"):
                self.ibuf[bi[f]].fill(_FILL[f])
            for f in ("lat", "lon", "elevation"):
                self.fbuf[bf[f]].fill(_FILL[f])

    # -- scanner-facing views (full-capacity, contiguous) -------------------

    def _irow(self, f: str) -> np.ndarray:
        return self.ibuf[_packed_layout()[2][f]]

    @property
    def device_id(self) -> np.ndarray:
        return self._irow("device_id")

    @property
    def mtype_id(self) -> np.ndarray:
        return self._irow("mtype_id")

    @property
    def ts_s(self) -> np.ndarray:
        return self._irow("ts_s")

    @property
    def ts_ns(self) -> np.ndarray:
        return self._irow("ts_ns")

    @property
    def update_state(self) -> np.ndarray:
        return self._irow("update_state")

    @property
    def value(self) -> np.ndarray:
        return self.fbuf[_packed_layout()[3]["value"]]

    def set_const(self, *, tenant_id: int, payload_ref: int) -> None:
        """Per-payload constants, applied as 0-stride broadcasts at
        commit (and materialized into their rows only on adoption)."""
        self.tenant_id = int(tenant_id)
        self.payload_ref = int(payload_ref)

    def abort(self) -> None:
        """Discard: nothing was shared, so nothing needs undoing."""
        self._open = False

    def commit(self) -> List[BatchPlan]:
        """Enqueue the ``self.n`` scanned rows (call under the intake
        lock, i.e. via the dispatcher's ``_take``).  Returns every plan
        that became ready, like :meth:`Batcher.add_arrays`."""
        b = self._batcher
        if not self._open:
            raise RuntimeError("reservation already committed/aborted")
        self._open = False
        n = self.n
        if n <= 0:
            return []
        # in-place NULL_ID rewrite (same contract as add_arrays): the C
        # table can hold ids at/past the registry capacity, and unknown
        # tokens are already NULL_ID.  The buffers are ours — no
        # defensive copy needed.
        d = self.device_id[:n]
        bad = (d < 0) | (d >= b.capacity)
        if b.n_shards > 1:
            # Sharded commit: the scanner wrote RESOLVED ids, so shard
            # routing is knowable here.  Segment-ordered payloads (each
            # shard's rows a contiguous run, runs in shard order) enqueue
            # zero-copy views of this buffer; anything else takes the
            # add_arrays gather lane (copies counted, unknown ids
            # round-robined there).
            return self._commit_sharded(b, n, bad)
        if bad.any():
            d[bad] = NULL_ID
        cols: Dict[str, np.ndarray] = {
            f: self._irow(f)[:n]
            for f in ("device_id", "mtype_id", "ts_s", "ts_ns",
                      "update_state")
        }
        cols["value"] = self.value[:n]
        cols["tenant_id"] = np.broadcast_to(
            np.int32(self.tenant_id), n)
        cols["payload_ref"] = np.broadcast_to(
            np.int32(self.payload_ref), n)
        for f in _COL_FIELDS:
            if f not in cols:
                cols[f] = np.broadcast_to(_FILL_0D[f], n)
        now = b.clock()
        b._pending[0].append(
            _Chunk(cols=cols, length=n, arrival=now, reserved=self))
        b._counts[0] += n
        if b._oldest is None:
            b._oldest = now
        plans: List[BatchPlan] = []
        while max(b._counts) >= b.seg:
            plans.append(b._emit())
        return plans

    def _commit_sharded(self, b: "Batcher", n: int,
                        bad: np.ndarray) -> List[BatchPlan]:
        """Sharded enqueue of the scanned rows.  The zero-copy lane
        requires every id in range and the shard sequence monotonically
        non-decreasing — then shard ``s``'s rows are one contiguous run
        and the chunk is a VIEW (``res_off`` records its buffer
        position, so a full-width segment-aligned reservation can be
        adopted outright by ``_emit``)."""
        d = self.device_id[:n]
        segmented = not bad.any()
        if segmented:
            shard = d // b.rows_per_shard
            if n > 1:
                segmented = bool((shard[:-1] <= shard[1:]).all())
        if not segmented:
            # Gather fallback: same routing/copy contract as columnar
            # intake (bad ids rewritten + round-robined there).  The
            # buffers are ours and never touched again — views are safe
            # to hand over.
            return b.add_arrays(
                _copy=False,
                device_id=d,
                mtype_id=self.mtype_id[:n],
                ts_s=self.ts_s[:n],
                ts_ns=self.ts_ns[:n],
                update_state=self.update_state[:n],
                value=self.value[:n],
                tenant_id=np.broadcast_to(np.int32(self.tenant_id), n),
                payload_ref=np.broadcast_to(np.int32(self.payload_ref), n),
            )
        now = b.clock()
        bounds = np.searchsorted(shard, np.arange(b.n_shards + 1))
        for s in range(b.n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            if lo == hi:
                continue
            cols: Dict[str, np.ndarray] = {
                f: self._irow(f)[lo:hi]
                for f in ("device_id", "mtype_id", "ts_s", "ts_ns",
                          "update_state")
            }
            cols["value"] = self.value[lo:hi]
            cols["tenant_id"] = np.broadcast_to(
                np.int32(self.tenant_id), hi - lo)
            cols["payload_ref"] = np.broadcast_to(
                np.int32(self.payload_ref), hi - lo)
            for f in _COL_FIELDS:
                if f not in cols:
                    cols[f] = np.broadcast_to(_FILL_0D[f], hi - lo)
            b._pending[s].append(_Chunk(
                cols=cols, length=hi - lo, arrival=now, reserved=self,
                res_off=lo))
            b._counts[s] += hi - lo
        if b._oldest is None:
            b._oldest = now
        plans: List[BatchPlan] = []
        while max(b._counts) >= b.seg:
            plans.append(b._emit())
        return plans

    def finalize_adopted(self, n: int) -> Dict[str, np.ndarray]:
        """Emission-time completion of an adopted full-width buffer:
        write validity, the per-payload constants and the padding fills
        into their rows, and return the host-column views."""
        BATCH_I, BATCH_F, bi, bf = _packed_layout()
        ibuf, fbuf = self.ibuf, self.fbuf
        valid = ibuf[bi["valid"]]
        valid[:n] = 1
        valid[n:] = 0
        ibuf[bi["tenant_id"]][:n] = self.tenant_id
        ibuf[bi["payload_ref"]][:n] = self.payload_ref
        if n < self.cap:
            ibuf[bi["tenant_id"]][n:] = _FILL["tenant_id"]
            ibuf[bi["payload_ref"]][n:] = _FILL["payload_ref"]
            for f in ("device_id", "mtype_id", "ts_s", "ts_ns",
                      "update_state"):
                ibuf[bi[f]][n:] = _FILL[f]
            fbuf[bf["value"]][n:] = _FILL["value"]
        host_cols = {f: ibuf[i] for i, f in enumerate(BATCH_I)}
        host_cols.update({f: fbuf[i] for i, f in enumerate(BATCH_F)})
        return host_cols


class BatchPlan:
    """A ready-to-dispatch batch plus its host-side bookkeeping.

    Every plan carries the packed wire form (``packed_i`` ``[12, B]``
    int32 / ``packed_f`` ``[4, B]`` float32, pipeline/packed.py) — what
    the dispatcher stages and steps, two transfers a plan — and
    ``host_cols``, the numpy columns it was built from, so egress never
    has to fetch the input batch back off the device: only step
    *outputs* cross the host boundary after dispatch.  ``batch`` is the
    same plan read as an :class:`EventBatch` (tests and reference
    comparisons); nothing on the served path builds it.
    """

    __slots__ = ("_batch", "n_events", "width", "full_width", "created_at",
                 "max_wait_s", "host_cols", "packed_i", "packed_f", "staged",
                 "seq", "reason", "dispatch_s", "released")

    def __init__(
        self,
        n_events: int = 0,
        # columns of this plan's buffers: the rung it was assembled at
        # (plan_rungs), which is the shape the device steps
        width: int = 1,
        created_at: float = 0.0,
        max_wait_s: float = 0.0,  # how long the oldest row waited
        host_cols: Optional[Dict[str, np.ndarray]] = None,
        packed_i: Optional[np.ndarray] = None,
        packed_f: Optional[np.ndarray] = None,
        # Device-resident (bi, bf) pair staged ahead of the step by the
        # dispatcher (pipeline/packed.py stage_packed_batch): the H2D
        # copy of plan N+1 overlaps plan N's device step.  None =
        # unstaged (sync transfer at step-call time, the CPU fallback).
        staged: Optional[tuple] = None,
        # Emission bookkeeping for the device-resident dispatch ring:
        # ``seq`` is the batcher's monotonic emission number (commit/
        # egress attribution of a chained step), ``reason`` the emit
        # trigger ("fill" | "deadline" | "idle" | "flush").  Only
        # full-width fill emissions ride the ring; the partials are
        # latency-sensitive and take the single-step path.
        seq: int = -1,
        reason: str = "fill",
        # Host dispatch time this plan paid (single-step: the jitted
        # call; ring slot: its 1/K share of the chain dispatch) —
        # flight-recorder stage attribution, stamped by the dispatcher.
        dispatch_s: float = 0.0,
        # The batcher's configured width (``pipeline.width``); None =
        # ``width``.  What "full" means to the ring and to ``fill``,
        # whatever rung the plan rides.
        full_width: Optional[int] = None,
    ):
        self._batch = None
        self.n_events = n_events
        self.width = width
        self.full_width = width if full_width is None else full_width
        self.created_at = created_at
        self.max_wait_s = max_wait_s
        self.host_cols = host_cols if host_cols is not None else {}
        self.packed_i = packed_i
        self.packed_f = packed_f
        self.staged = staged
        self.seq = seq
        self.reason = reason
        self.dispatch_s = dispatch_s
        # Set by the dispatcher when egress takes the plan off its
        # outstanding count (the commit gate's accounting).
        self.released = False

    @property
    def batch(self) -> Optional[EventBatch]:
        """The plan as an :class:`EventBatch` built (and cached) from
        ``host_cols`` — device transfers, so never under a lock."""
        if self._batch is None and self.host_cols:
            import jax.numpy as jnp

            self._batch = EventBatch(
                **{k: jnp.asarray(v) for k, v in self.host_cols.items()})
        return self._batch

    @property
    def fill(self) -> float:
        """Rows over the CONFIGURED width: a rung-full deadline plan is
        as partial as it was before it rode a narrow program."""
        return self.n_events / self.full_width


class AdaptiveBatchController:
    """Load-adaptive emission window (the deadline the batcher emits on).

    A plan's width is one of a few compiled into the jitted step
    (:func:`plan_rungs`) and follows its row count alone — the adaptive
    knob is the *time window* a partial batch may coalesce before the
    deadline forces it out.  The stream-processing
    literature identifies exactly this trade (arxiv 1807.07724 §5,
    2307.14287 §4): small windows chase the latency SLO, large windows
    chase throughput, and a static setting is wrong at one end or the
    other.  Decisions are made per EMIT (never per row) from signals the
    batcher already has:

    - a deadline emit at low fill with nothing left pending → the stream
      is idle; SHRINK the window toward ``min_s`` (less added latency);
    - a segment-fill emit, or a full batch still pending after an emit →
      the stream is backlogged; GROW the window toward ``max_s`` (fuller
      batches, fewer partial-width dispatches).

    The window binds only rows that have something to coalesce behind: a
    live wire payload that finds the pipeline empty leaves at once
    (reason "idle", :meth:`Batcher.emit_idle`) and waits for no window,
    so an idle emission, like a flush, tells the controller nothing.

    Deterministic: no internal clock — driven entirely by the batcher's
    emits, so a fake-clock test replays decisions exactly.  Decisions are
    exported through the metrics registry (``ingest.adaptive_window_s``
    gauge, ``ingest.adaptive_grow`` / ``ingest.adaptive_shrink``
    counters).
    """

    def __init__(
        self,
        deadline_ms: float = 5.0,
        min_ms: Optional[float] = None,
        max_ms: Optional[float] = None,
        low_fill: float = 0.25,
        grow: float = 1.5,
        shrink: float = 0.75,
        metrics=None,
    ):
        if grow <= 1.0 or not 0.0 < shrink < 1.0:
            raise ValueError("need grow > 1 and 0 < shrink < 1")
        self.window_s = deadline_ms / 1e3
        self.min_s = (min_ms if min_ms is not None else deadline_ms / 4) / 1e3
        self.max_s = (max_ms if max_ms is not None else deadline_ms * 8) / 1e3
        if not self.min_s <= self.window_s <= self.max_s:
            raise ValueError(
                f"deadline {self.window_s}s outside [{self.min_s}, {self.max_s}]")
        self.low_fill = low_fill
        self.grow = grow
        self.shrink = shrink
        self.grows = 0
        self.shrinks = 0
        if metrics is not None:
            self._m_window = metrics.gauge("ingest.adaptive_window_s")
            self._m_window.set(self.window_s)
            self._m_grow = metrics.counter("ingest.adaptive_grow")
            self._m_shrink = metrics.counter("ingest.adaptive_shrink")
        else:
            self._m_window = self._m_grow = self._m_shrink = None

    @property
    def deadline_s(self) -> float:
        return self.window_s

    def on_emit(self, n_events: int, width: int, pending: int,
                reason: str) -> None:
        """Observe one emission (``reason``: "fill" | "deadline" |
        "idle" | "flush") and adjust the window.  Flush emits are
        shutdown/drain artifacts and idle emits waited for no window:
        neither adapts."""
        if reason in ("flush", "idle"):
            return
        if reason == "fill" or pending >= width:
            new = min(self.window_s * self.grow, self.max_s)
            if new != self.window_s:
                self.window_s = new
                self.grows += 1
                if self._m_grow is not None:
                    self._m_grow.inc()
                    self._m_window.set(new)
        elif reason == "deadline" and pending == 0 \
                and n_events <= self.low_fill * width:
            new = max(self.window_s * self.shrink, self.min_s)
            if new != self.window_s:
                self.window_s = new
                self.shrinks += 1
                if self._m_shrink is not None:
                    self._m_shrink.inc()
                    self._m_window.set(new)


class Batcher:
    """Assembles routed, fixed-shape event batches (see module docstring).

    ``resolve_device(token) -> int`` / ``resolve_mtype(name) -> int`` /
    ``resolve_alert(name) -> int`` map edge strings to dense handles — in
    the full stack these are the management stores' lookup methods (the
    near-cache analog of ``CachedDeviceManagementApiChannel.java``).
    """

    def __init__(
        self,
        width: int,
        n_shards: int,
        registry_capacity: int,
        resolve_device: Callable[[str], int],
        resolve_mtype: Callable[[str], int],
        resolve_alert: Callable[[str], int],
        invocations=None,  # HandleSpace-like (mint/lookup) for
                           # invocation-token correlation
        deadline_ms: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
        metrics=None,
        controller: Optional[AdaptiveBatchController] = None,
    ):
        if width % n_shards != 0:
            raise ValueError(f"width={width} not divisible by n_shards={n_shards}")
        # Validate the routing invariant up front (same check as
        # shard_for_device, surfaced at construction).
        shard_for_device(0, registry_capacity, n_shards)
        self.width = width
        self.n_shards = n_shards
        self.seg = width // n_shards
        # The widths this batcher emits at (plan_rungs).  A mesh keeps
        # the one: a sharded plan is n_shards segments of ``seg`` rows,
        # and that segment length is also the dispatcher's key from a
        # batch row to its shard.
        self.rungs = plan_rungs(width) if n_shards == 1 else (width,)
        self.capacity = registry_capacity
        self.rows_per_shard = registry_capacity // n_shards
        self.resolve_device = resolve_device
        self.resolve_mtype = resolve_mtype
        self.resolve_alert = resolve_alert
        self.invocations = invocations
        self._deadline_s = deadline_ms / 1e3
        # Optional adaptive window: when set, the controller owns the
        # deadline (shrinks under idle, grows under backlog) and the
        # static value above is only the fallback after detach.
        self.controller = controller
        self.clock = clock
        self._pending: List[Deque[_Chunk]] = [
            collections.deque() for _ in range(n_shards)
        ]
        self._counts = [0] * n_shards
        self._oldest: Optional[float] = None
        self._rr = 0  # round-robin shard for unknown devices
        self.emitted_batches = 0
        self.emitted_events = 0
        # Bytes memcpy'd during batch assembly (intake copies + emission
        # slice copies; adopted reserved buffers contribute zero) — the
        # measured half of the zero-copy ingest story.
        self.copied_bytes = 0
        # registry fold-in (per EMIT, never per row): batch fill/wait are
        # the assemble-stage watermark the lag attribution story needs
        self.metrics = metrics
        if metrics is not None:
            self._m_batches = metrics.counter("ingest.batches_emitted")
            self._m_rows = metrics.counter("ingest.rows_emitted")
            self._m_rows_idle = metrics.counter("ingest.rows_emitted_idle")
            self._m_fill = metrics.gauge("ingest.batch_fill")
            self._m_wait = metrics.histogram("ingest.batch_wait_s")
            self._m_copied = metrics.counter("pipeline.bytes_copied.batch")
        else:
            self._m_copied = None
        # rows in shard s's segment of every emitted plan (their sum is
        # ingest.rows_emitted): the skew a mesh pays for — a plan goes
        # out when its FULLEST segment fills.  Mesh only.
        self._m_shard_rows = (
            [metrics.counter(f"ingest.shard_rows_emitted.{s}")
             for s in range(n_shards)]
            if metrics is not None and n_shards > 1 else None)

    @property
    def deadline_s(self) -> float:
        if self.controller is not None:
            return self.controller.deadline_s
        return self._deadline_s

    @deadline_s.setter
    def deadline_s(self, value: float) -> None:
        self._deadline_s = float(value)
        if self.controller is not None:
            # write-through: the attribute was a plain knob before the
            # controller existed, so an explicit set re-anchors the
            # adaptive window (still clamped to its [min_s, max_s])
            # instead of being silently shadowed by it
            c = self.controller
            c.window_s = min(max(float(value), c.min_s), c.max_s)
            if c._m_window is not None:
                c._m_window.set(c.window_s)

    # -- intake: scalar paths ------------------------------------------------

    def add(self, req: DecodedRequest, tenant_id: int, payload_ref: int) -> Optional[BatchPlan]:
        """Queue one decoded event; returns a plan if a segment filled."""
        et = req.event_type
        if et is None:
            raise ValueError(
                f"{req.kind.name} is a host-plane request, not a pipeline event"
            )
        return self._enqueue_row(
            device_id=self.resolve_device(req.device_token),
            tenant_id=tenant_id,
            event_type=int(et),
            ts_s=req.ts_s,
            ts_ns=req.ts_ns,
            mtype_id=self.resolve_mtype(req.mtype) if req.mtype else NULL_ID,
            value=req.value,
            lat=req.lat,
            lon=req.lon,
            elevation=req.elevation,
            alert_code=(self.resolve_alert(req.alert_type)
                        if req.alert_type else NULL_ID),
            alert_level=int(req.alert_level),
            # responses/invocations correlate through the invocation
            # token (reference: originatingEventId links a response to
            # its invocation event)
            command_id=self._invocation_id(req),
            payload_ref=payload_ref,
            update_state=bool(req.update_state),
        )

    def add_dense(
        self,
        *,
        device_id: int,
        tenant_id: int,
        event_type: int,
        ts_s: int,
        ts_ns: int = 0,
        mtype_id: int = NULL_ID,
        value: float = 0.0,
        lat: float = 0.0,
        lon: float = 0.0,
        elevation: float = 0.0,
        alert_code: int = NULL_ID,
        alert_level: int = 0,
        command_id: int = NULL_ID,
        payload_ref: int = NULL_ID,
        update_state: bool = False,
    ) -> Optional[BatchPlan]:
        """Queue one already-resolved row — the re-injection path for
        derived alerts and presence STATE_CHANGEs (reprocess-topic analog),
        which carry dense handles instead of edge strings.  Defaults to
        ``update_state=False``: system-generated events must not touch
        last-known state or presence."""
        return self._enqueue_row(
            device_id=int(device_id),
            tenant_id=int(tenant_id),
            event_type=int(event_type),
            ts_s=int(ts_s),
            ts_ns=int(ts_ns),
            mtype_id=int(mtype_id),
            value=float(value),
            lat=float(lat),
            lon=float(lon),
            elevation=float(elevation),
            alert_code=int(alert_code),
            alert_level=int(alert_level),
            command_id=int(command_id),
            payload_ref=int(payload_ref),
            update_state=bool(update_state),
        )

    def _enqueue_row(self, **values) -> Optional[BatchPlan]:
        """Shared routing/append/deadline/emit tail of the scalar paths."""
        device_id = values["device_id"]
        if 0 <= device_id < self.capacity:
            shard = device_id // self.rows_per_shard
        else:
            values["device_id"] = NULL_ID
            shard = self._rr = (self._rr + 1) % self.n_shards
        now = self.clock()
        q = self._pending[shard]
        tail = q[-1] if q else None
        if tail is None or tail.length >= tail.capacity:
            tail = _Chunk(
                cols={f: np.empty(self.seg, _DTYPE[f]) for f in _COL_FIELDS},
                length=0,
                arrival=now,
            )
            q.append(tail)
        i = tail.length
        for f in _COL_FIELDS:
            tail.cols[f][i] = values[f]
        tail.length = i + 1
        self._counts[shard] += 1
        if self._oldest is None:
            self._oldest = now
        if self._counts[shard] >= self.seg:
            return self._emit()
        return None

    # -- intake: vectorized paths -------------------------------------------

    def add_arrays(self, _copy: bool = True, **columns) -> List[BatchPlan]:
        """Columnar intake: queue N pre-resolved rows from 1-D arrays.

        ``device_id`` is required; any other batch column
        (:data:`_COL_FIELDS`) may be supplied as an array of the same
        length or omitted to take its fill value.  Returns every plan that
        became ready (possibly several when N spans multiple segments).
        This is the 1M events/sec/chip intake edge: one gather per field
        per shard, no Python per-row work.

        ``_copy=False`` is for internal callers that hand over freshly
        built arrays they will never touch again; external callers keep
        the default so refilling their buffers cannot corrupt queued rows.
        """
        device_id = np.asarray(columns["device_id"], np.int32)
        n = len(device_id)
        if n == 0:
            return []
        cols: Dict[str, np.ndarray] = {}
        filled: set = set()
        for f in _COL_FIELDS:
            v = columns.get(f)
            if f == "device_id":
                cols[f] = device_id
            elif v is None:
                # Zero-alloc fill: a 0-stride read-only broadcast of the
                # per-field template, never a fresh np.full per call —
                # emission copies by slice regardless, and nothing writes
                # into queued chunk columns.
                cols[f] = np.broadcast_to(_FILL_0D[f], n)
                filled.add(f)
            else:
                if not (type(v) is np.ndarray and v.dtype == _DTYPE[f]
                        and v.ndim == 1):
                    # already-typed 1-D inputs skip the asarray sweep
                    v = np.asarray(v, _DTYPE[f])
                cols[f] = v
                if len(v) != n:
                    raise ValueError(
                        f"column {f!r} length {len(v)} != {n}")
        unknown_keys = set(columns) - set(_COL_FIELDS)
        if unknown_keys:
            raise ValueError(f"unknown columns {sorted(unknown_keys)}")

        in_range = (device_id >= 0) & (device_id < self.capacity)
        if self.n_shards == 1:
            shard = None  # everything lands on shard 0
            if not in_range.all():
                cols["device_id"] = np.where(in_range, device_id, NULL_ID)
        else:
            shard = device_id // self.rows_per_shard
            bad = ~in_range
            if bad.any():
                k = int(bad.sum())
                shard[bad] = (self._rr + np.arange(k)) % self.n_shards
                self._rr = (self._rr + k) % self.n_shards
                cols["device_id"] = np.where(bad, NULL_ID, device_id)

        now = self.clock()
        if self.n_shards == 1:
            # Copy caller-backed columns: np.asarray above is zero-copy for
            # matching dtypes, and rows can sit queued past this call (up
            # to the deadline) — a caller refilling its buffers must not
            # corrupt queued events.  (The multi-shard path copies via its
            # boolean-mask gather already.)
            if _copy:
                # Fill broadcasts are immutable templates — copying them
                # would just re-materialize the np.full this path dropped.
                copied = {
                    f for f, c in cols.items()
                    if f not in filled
                    and (c is columns.get(f) or c.base is not None)
                }
                self._count_copied(sum(cols[f].nbytes for f in copied))
                cols = {
                    f: (np.array(c, copy=True) if f in copied else c)
                    for f, c in cols.items()
                }
            self._pending[0].append(_Chunk(cols=cols, length=n, arrival=now))
            self._counts[0] += n
        else:
            for s in range(self.n_shards):
                m = shard == s
                c = int(m.sum())
                if c == 0:
                    continue
                self._pending[s].append(_Chunk(
                    cols={f: cols[f][m] for f in _COL_FIELDS},
                    length=c,
                    arrival=now,
                ))
                self._count_copied(c * (_ROW_BYTES - 1))  # mask gathers
                self._counts[s] += c
        if self._oldest is None:
            self._oldest = now

        plans: List[BatchPlan] = []
        while max(self._counts) >= self.seg:
            plans.append(self._emit())
        return plans

    def reserve(self, cap: int) -> Optional["Reservation"]:
        """Hand out a :class:`Reservation` of up to ``cap`` rows for the
        fill-direct wire scanner, or None when ineligible (a payload
        wider than one batch cannot land in one emission).  Sharded
        batchers reserve too: the scanner writes RESOLVED device ids, so
        ``commit`` routes by shard after the scan — a segment-ordered
        full-width payload is adopted zero-copy exactly like the
        single-shard case, and anything else falls back to the gather
        lane.  The buffers are private until ``commit`` — reserve is
        safe from any thread."""
        if not 0 < cap <= self.width:
            return None
        return Reservation(self, cap)

    def _count_copied(self, nbytes: int) -> None:
        if nbytes:
            self.copied_bytes += nbytes
            if self._m_copied is not None:
                self._m_copied.inc(nbytes)

    def _invocation_id(self, req: DecodedRequest) -> int:
        """Invocation rows MINT their token (host- or replay-created);
        responses only LOOK UP, so a device sending garbage
        originatingEventId values cannot permanently allocate handles —
        the unknown token just stays uncorrelated (NULL_ID)."""
        inv = self.invocations
        if inv is None or not req.originating_event:
            return NULL_ID
        from sitewhere_tpu.ingest.decoders import RequestKind

        if req.kind == RequestKind.COMMAND_INVOCATION:
            return inv.mint(req.originating_event)
        return inv.lookup(req.originating_event)

    def add_requests(
        self,
        reqs: Sequence[DecodedRequest],
        tenant_ids: Sequence[int],
        payload_refs: Sequence[int],
    ) -> List[BatchPlan]:
        """Batch intake of decoded requests: one token-resolution pass
        builds the column arrays, then :meth:`add_arrays`."""
        n = len(reqs)
        if n == 0:
            return []
        out = {f: np.empty(n, _DTYPE[f]) for f in _COL_FIELDS}
        rd, rm, ra = self.resolve_device, self.resolve_mtype, self.resolve_alert
        for i, req in enumerate(reqs):
            et = req.event_type
            if et is None:
                raise ValueError(
                    f"{req.kind.name} is a host-plane request, not a pipeline event"
                )
            out["device_id"][i] = rd(req.device_token)
            out["event_type"][i] = int(et)
            out["ts_s"][i] = req.ts_s
            out["ts_ns"][i] = req.ts_ns
            out["mtype_id"][i] = rm(req.mtype) if req.mtype else NULL_ID
            out["value"][i] = req.value
            out["lat"][i] = req.lat
            out["lon"][i] = req.lon
            out["elevation"][i] = req.elevation
            out["alert_code"][i] = ra(req.alert_type) if req.alert_type else NULL_ID
            out["alert_level"][i] = int(req.alert_level)
            out["update_state"][i] = bool(req.update_state)
            # invocation-token correlation, same contract as add()
            out["command_id"][i] = self._invocation_id(req)
        out["tenant_id"][:] = np.asarray(tenant_ids, np.int32)
        out["payload_ref"][:] = np.asarray(payload_refs, np.int32)
        return self.add_arrays(_copy=False, **out)  # freshly built here

    # -- deadline/flush ------------------------------------------------------

    def poll(self) -> Optional[BatchPlan]:
        """Emit on deadline: call periodically from the dispatch loop."""
        if self._oldest is None:
            return None
        if self.clock() - self._oldest >= self.deadline_s:
            return self._emit(reason="deadline")
        return None

    def emit_idle(self) -> Optional[BatchPlan]:
        """Emit what is pending now, at the narrow rung a deadline plan
        would take: the caller (the dispatcher's live wire intake, under
        its intake lock) has seen that these rows found the pipeline
        empty, so a deadline wait would coalesce them with nothing."""
        if self._oldest is None:
            return None
        plan = self._emit(reason="idle")
        if self.metrics is not None:
            self._m_rows_idle.inc(plan.n_events)
        return plan

    def flush(self) -> Optional[BatchPlan]:
        """Emit whatever is pending (shutdown/drain)."""
        if self._oldest is None:
            return None
        return self._emit(reason="flush")

    @property
    def pending(self) -> int:
        return sum(self._counts)

    # -- emission -----------------------------------------------------------

    def _emit_tail(self, n: int, reason: str):
        """Shared emission bookkeeping: wait accounting, counters,
        adaptive-controller feedback.  Returns ``(now, wait)``."""
        now = self.clock()
        wait = now - self._oldest if self._oldest is not None else 0.0
        # Carried-over rows keep their chunk arrival time for the deadline
        # (plain min-scan: no per-emit list on the hot path).
        oldest = None
        for q in self._pending:
            if q and (oldest is None or q[0].arrival < oldest):
                oldest = q[0].arrival
        self._oldest = oldest
        self.emitted_batches += 1
        self.emitted_events += n
        if self.metrics is not None:
            self._m_batches.inc()
            self._m_rows.inc(n)
            self._m_fill.set(n / self.width)
            self._m_wait.observe(wait)
        if self.controller is not None:
            self.controller.on_emit(n, self.width, self.pending, reason)
        return now, wait

    def _adoptable_sharded(self) -> bool:
        """True when every shard's sole pending chunk is the matching
        segment of ONE full-width reservation — ``_commit_sharded`` left
        segment-aligned views, so the reserved buffers already ARE the
        batch and ``_emit_adopted`` can ship them without a copy."""
        res = None
        for s in range(self.n_shards):
            q = self._pending[s]
            if len(q) != 1:
                return False
            ch = q[0]
            if ch.reserved is None or ch.start != 0 \
                    or ch.length != self.seg \
                    or ch.res_off != s * self.seg:
                return False
            if res is None:
                res = ch.reserved
            elif ch.reserved is not res:
                return False
        return res is not None and res.cap == self.width

    @hot_path
    def _emit_adopted(self, reason: str) -> BatchPlan:
        """Zero-copy emission: the pending chunk(s) are a full-width
        reserved segment — its packed buffers BECOME the batch.  Only
        validity, the per-payload constants and any padding are written;
        no row data moves.  (Sharded: one view-chunk per shard, all of
        the same reservation, popped together.)"""
        res = None
        n = 0
        for s in range(self.n_shards):
            ch = self._pending[s].popleft()
            res = ch.reserved
            n += ch.length
            self._counts[s] -= ch.length
            if self._m_shard_rows is not None:
                self._m_shard_rows[s].inc(ch.length)
        host_cols = res.finalize_adopted(n)
        now, wait = self._emit_tail(n, reason)
        return BatchPlan(
            n_events=n, width=self.width, created_at=now,
            max_wait_s=wait, host_cols=host_cols,
            packed_i=res.ibuf, packed_f=res.fbuf,
            seq=self.emitted_batches - 1, reason=reason,
        )

    def _rung(self, rows: int) -> int:
        """The width the copying lane emits ``rows`` pending rows at:
        the smallest rung that holds them (a plan never takes more than
        the configured width, the last rung and a mesh's only one).
        Nothing but the row count decides."""
        for width in self.rungs:
            if width >= rows:
                return width
        return self.width

    def _assemble_buffers(self, width: int):
        """Fallback batch-assembly buffers, ``width`` columns wide — the
        copying lane's allocations, off the adopted path.  Full-width
        fill emissions (single-shard AND segment-ordered sharded
        reservations) adopt the reservation's packed buffers and never
        come here; this
        allocates only for the mixed/deadline/flush leftovers whose rows
        genuinely have to be gathered out of multiple chunks.

        The host columns are built directly as rows of the packed wire
        buffers — ``_emit``'s fill loop writes through the ``out``
        views, so emission costs no extra pass.  Bool columns keep their
        own arrays (host_cols consumers expect bool dtype) and land in
        their int rows at the end."""
        from sitewhere_tpu.pipeline.packed import BATCH_F, BATCH_I

        ibuf = np.empty((len(BATCH_I), width), np.int32)
        fbuf = np.empty((len(BATCH_F), width), np.float32)
        out = {}
        for i, f in enumerate(BATCH_I):
            if f in ("valid", "update_state"):
                out[f] = np.full(width, _FILL[f], np.bool_)
            else:
                ibuf[i].fill(_FILL[f])
                out[f] = ibuf[i]
        for i, f in enumerate(BATCH_F):
            fbuf[i].fill(_FILL[f])
            out[f] = fbuf[i]
        out["valid"][:] = False
        return ibuf, fbuf, out

    @hot_path
    def _emit(self, reason: str = "fill") -> BatchPlan:
        q = self._pending[0]
        if self.n_shards == 1:
            if len(q) == 1 and q[0].reserved is not None \
                    and q[0].start == 0 \
                    and q[0].reserved.cap == self.width:
                return self._emit_adopted(reason)
        elif q and q[0].reserved is not None \
                and self._adoptable_sharded():
            return self._emit_adopted(reason)
        width = self._rung(self.pending)
        seg = width // self.n_shards
        ibuf, fbuf, out = self._assemble_buffers(width)
        n = 0
        for s in range(self.n_shards):
            base = s * seg
            filled = 0
            q = self._pending[s]
            while filled < seg and q:
                ch = q[0]
                take = min(ch.length - ch.start, seg - filled)
                lo, hi = base + filled, base + filled + take
                for f in _COL_FIELDS:
                    out[f][lo:hi] = ch.cols[f][ch.start:ch.start + take]
                out["valid"][lo:hi] = True
                ch.start += take
                filled += take
                if ch.start >= ch.length:
                    # Fully drained (staging chunks included — dropping
                    # them keeps a later append from resurrecting
                    # already-emitted rows).
                    q.popleft()
            self._counts[s] -= filled
            n += filled
            if self._m_shard_rows is not None:
                self._m_shard_rows[s].inc(filled)
        self._count_copied(n * _ROW_BYTES)

        now, wait = self._emit_tail(n, reason)
        from sitewhere_tpu.pipeline.packed import BATCH_I

        ibuf[BATCH_I.index("valid")] = out["valid"]
        ibuf[BATCH_I.index("update_state")] = out["update_state"]
        self._count_copied(2 * 4 * width)  # bool→int32 rows
        return BatchPlan(
            n_events=n, width=width, created_at=now,
            max_wait_s=wait, host_cols=out, packed_i=ibuf, packed_f=fbuf,
            seq=self.emitted_batches - 1, reason=reason,
            full_width=self.width,
        )

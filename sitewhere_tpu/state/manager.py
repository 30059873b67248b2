"""DeviceStateManager: owner + query surface of the DeviceState tensors.

Reference: ``service-device-state`` is the queryable materialized view of
last-known device state (``grpc/DeviceStateImpl.java`` + Mongo persistence
``MongoDeviceStateManagement``) fed by the enriched-events consumer.  Here
the view *is* the :class:`~sitewhere_tpu.schema.DeviceState` pytree the
pipeline step threads through every batch; this manager holds the current
epoch, applies step outputs, answers host queries (single-device reads,
missing/recent scans), and runs the presence sweep against it.

Device-resident by design: queries that scan all devices (missing list,
recently-seen) are vectorized reductions on device, with only the
resulting indices/rows copied back.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.ids import NULL_ID, IdentityMap
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent
from sitewhere_tpu.schema import DeviceState, EventBatch
from sitewhere_tpu.services.common import EntityNotFound, require
from sitewhere_tpu.state.presence import (
    presence_sweep,
    state_change_columns,
)


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


@functools.lru_cache(maxsize=64)
def _partition_gather(rung: int):
    """Jitted padded gather for one partition rung size — compiled ONCE
    per pow2 rung and shared by every tenant sitting on that rung, the
    same bucketing guarantee the rules compiler gives program shapes.
    Padding rows gather device 0 and carry valid=False."""
    del rung  # the cache key; the jit specializes on idx.shape

    @jax.jit
    def gather(state, idx, valid):
        rows = jax.tree.map(lambda a: a[idx], state)
        return rows, valid

    return gather


class TenantPartitions:
    """Per-tenant pow2 capacity ladders over the shared state tensors.

    The global :class:`DeviceState` epoch is a single fixed-capacity
    tensor — it never resizes, so tenant isolation at this layer means
    each tenant's QUERY/EXPORT surface runs through its own padded
    partition view: a gather of the tenant's device rows padded to a
    pow2 rung.  Rungs ride a sticky ladder (grow to the next pow2 when
    the tenant's device count exceeds the rung, shrink only once count
    falls to a quarter of it — the registry-ladder hysteresis from the
    rules subsystem), so registration churn inside one tenant bumps
    only THAT tenant's rung.  ``compile_count`` counts a tenant's rung
    transitions — the churn-storm bench pins it flat for untouched
    tenants while a noisy neighbor registers devices in waves.  The
    gather kernel itself is cached per RUNG (module-level), so two
    tenants on the same rung share one compiled executable.
    """

    def __init__(self, tenant_column_provider,
                 min_capacity: int = 64, metrics=None):
        self._provider = tenant_column_provider
        self.min_capacity = _next_pow2(max(1, int(min_capacity)))
        self._lock = threading.Lock()
        # tenant_id → {"count", "rung", "compile_count"}
        self._parts: Dict[int, Dict[str, int]] = {}
        self._column: Optional[np.ndarray] = None
        self._m_tracked = None
        self._m_compiles = None
        self._m_resizes = None
        if metrics is not None:
            self.bind_metrics(metrics)

    def bind_metrics(self, metrics) -> None:
        self._m_tracked = metrics.gauge("tenant.partition.tracked")
        self._m_compiles = metrics.counter("tenant.partition.compiles")
        self._m_resizes = metrics.counter("tenant.partition.resizes")

    def refresh(self) -> None:
        """Re-derive per-tenant device counts from the registry mirror's
        tenant column and walk each tenant's rung ladder.  O(capacity)
        bincount — called from query surfaces and on a registration
        cadence, never from the step hot path."""
        col = np.asarray(self._provider())
        owned = col[col >= 0]
        counts = (np.bincount(owned) if owned.size
                  else np.zeros(0, np.int64))
        tenants = np.nonzero(counts)[0]
        with self._lock:
            self._column = col
            for t in tenants.tolist():
                count = int(counts[t])
                part = self._parts.get(t)
                if part is None:
                    self._parts[t] = {
                        "count": count,
                        "rung": max(self.min_capacity, _next_pow2(count)),
                        "compile_count": 1,
                    }
                    if self._m_compiles is not None:
                        self._m_compiles.inc()
                    continue
                part["count"] = count
                rung = part["rung"]
                if count > rung:
                    part["rung"] = _next_pow2(count)
                elif (count <= rung // 4
                      and rung > self.min_capacity):
                    # shrink-at-quarter hysteresis: a tenant oscillating
                    # around a rung boundary never flaps its kernel
                    part["rung"] = max(self.min_capacity,
                                       _next_pow2(count))
                if part["rung"] != rung:
                    part["compile_count"] += 1
                    if self._m_compiles is not None:
                        self._m_compiles.inc()
                    if self._m_resizes is not None:
                        self._m_resizes.inc()
            if self._m_tracked is not None:
                self._m_tracked.set(len(self._parts))

    def tenants(self) -> List[int]:
        with self._lock:
            return sorted(self._parts)

    def compile_count(self, tenant_id: int) -> int:
        with self._lock:
            part = self._parts.get(int(tenant_id))
            return 0 if part is None else part["compile_count"]

    def partition_of(self, tenant_id: int) -> Optional[Dict[str, int]]:
        with self._lock:
            part = self._parts.get(int(tenant_id))
            return None if part is None else dict(part)

    def indices_of(self, tenant_id: int):
        """``(idx, valid)`` for one tenant's partition view: the
        tenant's device ids padded to its rung (padding gathers row 0,
        masked out by ``valid``).  None if the tenant owns nothing."""
        with self._lock:
            part = self._parts.get(int(tenant_id))
            col = self._column
        if part is None or col is None:
            return None
        ids = np.nonzero(col == int(tenant_id))[0].astype(np.int32)
        rung = part["rung"]
        idx = np.zeros(rung, np.int32)
        valid = np.zeros(rung, bool)
        n = min(len(ids), rung)
        idx[:n] = ids[:n]
        valid[:n] = True
        return idx, valid

    def view(self, state, tenant_id: int):
        """Padded per-tenant gather of ``state`` — ``(rows, valid)`` on
        device, through the rung-cached jitted gather."""
        iv = self.indices_of(tenant_id)
        if iv is None:
            return None
        idx, valid = iv
        gather = _partition_gather(len(idx))
        return gather(state, jnp.asarray(idx), jnp.asarray(valid))

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {
                "tenants": len(self._parts),
                "min_capacity": self.min_capacity,
                "partitions": {str(t): dict(p)
                               for t, p in sorted(self._parts.items())},
            }


def _packed_codecs():
    """Module-level jitted pack/unpack (lazy import breaks the cycle;
    per-call ``jax.jit(...)`` would retrace every time)."""
    global _PACK, _UNPACK
    if "_PACK" not in globals():
        from sitewhere_tpu.pipeline.packed import pack_state, unpack_state

        _PACK = jax.jit(pack_state)
        _UNPACK = jax.jit(unpack_state)
    return _PACK, _UNPACK


def _packed_sweep():
    """Module-level jitted :func:`packed_presence_sweep` (lazy import, as
    above).  Not donated, so it pays one copy of the carry.  Every
    single step now takes the epoch under the manager's lock
    (:meth:`DeviceStateManager.step_packed`), so a sweep dispatched
    under the same lock could donate it too (ROADMAP S12)."""
    global _SWEEP
    if "_SWEEP" not in globals():
        from sitewhere_tpu.pipeline.packed import packed_presence_sweep

        _SWEEP = jax.jit(packed_presence_sweep)
    return _SWEEP


@jax.jit
def _merge_presence(new_rows, cur_rows, present_now):
    """Packed-form presence reconciliation (see :meth:`commit` docstring):
    a concurrent sweep's missing flags survive unless THIS step merged an
    event for the device."""
    from sitewhere_tpu.pipeline.packed import PRESENCE_LANE

    merged = (new_rows[:, PRESENCE_LANE] != 0) | (
        (cur_rows[:, PRESENCE_LANE] != 0) & ~present_now)
    return new_rows.at[:, PRESENCE_LANE].set(merged.astype(new_rows.dtype))


class DeviceStateManager(LifecycleComponent):
    """Holds the authoritative :class:`DeviceState` epoch.

    The pipeline dispatcher calls :meth:`commit` with each step's
    ``new_state``; readers get consistent snapshots.  ``tenant_ids`` for
    presence StateChange emission come from the registry mirror columns
    (the enrichment source of truth).
    """

    def __init__(
        self,
        capacity: int,
        identity: IdentityMap,
        num_mtype_slots: int = 8,
        tenant_id_of_device=None,  # Callable[[np.ndarray], np.ndarray]
        num_ewma_scales: int = 3,
    ):
        super().__init__(name="device-state-manager")
        from sitewhere_tpu.runtime.metrics import MetricsRegistry

        # a registry of its own until the instance binds its own
        self.bind_metrics(MetricsRegistry())
        self.identity = identity
        self._lock = threading.RLock()
        self._state: Optional[DeviceState] = DeviceState.empty(
            capacity, num_mtype_slots, num_ewma_scales)
        # Packed twin of the epoch (pipeline/packed.py): the dispatcher's
        # steady-state carry.  Exactly one of the two may be stale (None);
        # each is materialized lazily from the other so sweeps/queries and
        # the packed step loop never force each other's representation.
        self._packed = None
        self._tenant_id_of_device = tenant_id_of_device
        # Monotonic count of lease_packed() calls — the device-fault
        # containment protocol's observable: a failed donated chain is
        # recovered by simply leasing AGAIN from the still-held epoch, so
        # "re-leased without restart" is `lease_generation` advancing on
        # one live manager (tools/devfault_bench.py asserts exactly this).
        self.lease_generation = 0
        # Tenant-partitioned query views (attach_partitions): per-tenant
        # pow2 rung ladders over the shared tensors, so one tenant's
        # registration churn recompiles only its own partition view
        self.partitions: Optional[TenantPartitions] = None

    def bind_metrics(self, metrics) -> None:
        """The presence sweep's instruments (a ``Timer.time()`` region
        is a profiler span of the timer's name): ``presence.sweep_s`` is
        the whole of :meth:`apply_presence_sweep`; ``presence.sweeps``
        counts sweeps, ``presence.reported`` the rows handed on for
        re-injection.  The sweep program's own time is the device
        trace's, under the ``presence_sweep`` scope."""
        self._m_sweep = metrics.timer("presence.sweep_s")
        self._m_sweeps = metrics.counter("presence.sweeps")
        self._m_reported = metrics.counter("presence.reported")

    def attach_partitions(self, tenant_column_provider,
                          min_capacity: int = 64,
                          metrics=None) -> TenantPartitions:
        """Wire the tenant-partition ladder (instance passes the registry
        mirror's tenant column provider)."""
        self.partitions = TenantPartitions(
            tenant_column_provider, min_capacity=min_capacity,
            metrics=metrics)
        return self.partitions

    def tenant_state_summary(self, tenant_id: int) -> Dict[str, object]:
        """Per-tenant state summary through the tenant's partition view:
        the partitioned analog of :meth:`summary`.  Snapshot under the
        lock, gather + transfer OUTSIDE it (the lease lock must never
        ride a D2H — see missing_device_ids)."""
        require(self.partitions is not None,
                EntityNotFound("tenant partitions are not attached"))
        self.partitions.refresh()
        part = self.partitions.partition_of(tenant_id)
        if part is None:
            return {"devices": 0, "capacity": 0, "compile_count": 0,
                    "devices_with_state": 0, "devices_missing": 0}
        with self._lock:
            s = self.current
        view = self.partitions.view(s, tenant_id)
        if view is None:   # raced a refresh that dropped the column
            return {"devices": part["count"], "capacity": part["rung"],
                    "compile_count": part["compile_count"],
                    "devices_with_state": 0, "devices_missing": 0}
        rows, valid = view
        valid = np.asarray(valid)
        has = np.asarray(rows.last_event_type != NULL_ID) & valid
        missing = np.asarray(rows.presence_missing) & valid
        return {
            "devices": part["count"],
            "capacity": part["rung"],
            "compile_count": part["compile_count"],
            "devices_with_state": int(has.sum()),
            "devices_missing": int(missing.sum()),
        }

    # -- epoch plumbing ----------------------------------------------------

    @property
    def current(self) -> DeviceState:
        with self._lock:
            if self._state is None:
                _, unpack = _packed_codecs()
                self._state = unpack(self._packed)
            return self._state

    @property
    def current_packed(self):
        """The packed epoch (pack lazily after an unpacked commit)."""
        with self._lock:
            if self._packed is None:
                pack, _ = _packed_codecs()
                self._packed = pack(self.current)
            return self._packed

    def lease_packed(self):
        """Exclusive hand-off of the packed epoch for a DONATED step
        chain (the device-resident dispatch loop's carry).

        Donation deletes the input buffers once the chain runs, so the
        manager must stop being a co-owner: the unpacked twin is
        materialized FIRST (one async unpack dispatch — readers arriving
        mid-chain see the pre-chain epoch from fresh buffers, never the
        donated ones) and ``_packed`` is dropped.  Returns
        ``(packed, lease_token)``; pass the token to :meth:`commit_packed`
        so it can tell whether anything (a presence sweep, a migration
        import) intervened during the chain.

        If the chain crashes before commit, the manager simply still
        holds the pre-chain epoch — the chain's plans stay outstanding
        and journal replay re-steps them (at-least-once), identical to a
        single-step dispatch failure.  The dispatcher's containment path
        leans on exactly that: recovery NEVER touches the donated
        ``packed`` again (its buffers may be deleted — swlint DN001
        guards this statically); it re-leases a fresh pack of the held
        epoch and re-dispatches the re-parked plans single-step.
        """
        with self._lock:
            packed = self.current_packed
            if self._state is None:
                _, unpack = _packed_codecs()
                self._state = unpack(packed)
            self._packed = None
            self.lease_generation += 1
            # token = the materialized twin's identity: every out-of-band
            # state write (commit/sweep/import) replaces _state, so
            # `self._state is token` at commit time means nothing
            # intervened and the presence merge can be skipped
            return packed, self._state

    def step_packed(self, step, tables, bi, bf, place=None):
        """Hand the packed epoch to ONE single step and adopt its output,
        all under the lock: ``step(tables, packed, bi, bf)`` (the epoch
        laid out by ``place`` first, on a mesh) returns ``(new_packed,
        *outputs)``; the whole tuple is returned.

        The step may donate the carry (``build_packed_step(donate=
        True)``): nothing can read ``_packed`` between the read and the
        commit (a sweep, a checkpoint's ``current``, a REST reader all
        take this lock), so no presence merge is ever needed and none is
        dispatched.  Dispatch is asynchronous, so the lock is held for
        the host's dispatch alone.  The readers that leave the lock with
        an epoch hold the unpacked twin (``current``), which no hand-off
        donates; a program on the packed epoch is dispatched under the
        lock (``current`` unpacks, the sweep and its warm-up run there).

        A step that raises before its program is enqueued (an injected
        device fault, a trace error) leaves the epoch as it was; a retry
        reads it again through this method.  An execution fault surfaced
        after the enqueue has consumed a donated carry, as a failed
        chain's has (``lease_packed``)."""
        with self._lock:
            packed = self.current_packed
            if place is not None:
                packed = place(packed)
            out = step(tables, packed, bi, bf)
            self._packed = out[0]
            self._state = None
            return out

    def commit_packed(self, new_packed, present_now,
                      read_epoch=None, lease_token=None) -> None:
        """Adopt a step chain's output state (the packed-loop analog of
        :meth:`commit`; a single step commits inside
        :meth:`step_packed`): re-apply ``presence_missing`` flags a
        concurrent sweep set on the current epoch for devices the chain
        did not merge (``present_now`` = the chain's OR'd winner map).

        Pass ``read_epoch`` (the PackedState the chain consumed): when the
        current epoch is still that object, nothing intervened and the
        merge — an extra dispatch — is skipped entirely.  A donated chain
        passes ``lease_token`` from :meth:`lease_packed` instead (the
        consumed epoch's buffers no longer exist to compare).
        """
        with self._lock:
            unchanged = (
                (read_epoch is not None and self._packed is read_epoch)
                or (lease_token is not None and self._state is lease_token))
            if not unchanged:
                cur = self.current_packed
                new_packed = new_packed.replace(rows=_merge_presence(
                    new_packed.rows, cur.rows, present_now))
            self._packed = new_packed
            self._state = None

    def commit(self, new_state: DeviceState,
               batch: Optional[EventBatch] = None,
               accepted=None, present_now=None) -> None:
        """Adopt a pipeline step's output state (the merge already ran on
        device inside the step).

        Pass the step's ``present_now`` output (``bool[capacity]``, the
        devices the step actually merged) — or the ``batch`` it consumed
        plus the ``accepted`` mask to re-derive it — so a presence sweep
        that ran concurrently (between the dispatcher's read and this
        commit) is not lost: ``presence_missing`` flags on the current
        epoch are re-applied for devices the step did not actually merge.
        Rows the step REJECTED (unregistered/unassigned/tenant mismatch)
        never cleared presence in the step, so they must not count as
        touched here either.  Computed on device — no host transfer on the
        hot path; the ``present_now`` form also costs no extra scatter
        (the step derived it from its winner map).
        """
        with self._lock:
            current = self.current
            if current is not new_state and (
                    present_now is not None or batch is not None):
                cap = new_state.capacity
                if present_now is not None:
                    touched = present_now
                else:
                    # mirror the step's merge mask: update_state=False rows
                    # never cleared presence in the step
                    merged_rows = (batch.valid & (batch.device_id >= 0)
                                   & batch.update_state)
                    if accepted is not None:
                        merged_rows = merged_rows & accepted
                    ids = jnp.where(merged_rows, batch.device_id, cap)
                    touched = jnp.zeros((cap,), bool).at[ids].set(
                        True, mode="drop")
                merged = new_state.presence_missing | (
                    current.presence_missing & ~touched
                )
                new_state = new_state.replace(presence_missing=merged)
            self._state = new_state
            self._packed = None

    # -- presence ----------------------------------------------------------

    def apply_presence_sweep(
        self, now_s: int, missing_after_s: int
    ) -> Optional[Dict[str, np.ndarray]]:
        """Run the sweep, adopt the flagged epoch, and build the report:
        the STATE_CHANGE rows of the newly-missing devices as host
        columns for ``ingest_arrays`` (None if none).

        Which lane: where the manager holds the packed epoch (every
        served deployment between two steps) the sweep runs ON it —
        :func:`packed_presence_sweep` reads three lanes of the carry and
        writes one — and its output IS the next packed epoch: ``_packed``
        is kept, the unpacked twin is dropped (``current`` unpacks lazily
        if a query wants it), so the step after a sweep reads the carry
        the sweep wrote and nothing is unpacked or re-packed.  A step
        that read the epoch before the sweep and commits after it finds
        ``_packed`` another object and merges the sweep's flags in
        (:meth:`commit_packed`).  Where only the unpacked form is held —
        an unpacked deployment's ``commit``, or a chain holding the
        packed lease (the twin then IS the held epoch) — the unpacked
        :func:`presence_sweep` runs on it as before.

        The report is numpy from the mask on: no program depends on how
        many devices went silent, so none is compiled for a new count.
        """
        with self._m_sweep.time():
            with self._lock:
                if self._packed is not None:
                    self._packed, newly = _packed_sweep()(
                        self._packed, jnp.int32(now_s),
                        jnp.int32(missing_after_s))
                    self._state = None
                else:
                    self._state, newly = presence_sweep(
                        self.current, jnp.int32(now_s),
                        jnp.int32(missing_after_s))
            self._m_sweeps.inc()
            # the mask's fetch waits for the chip outside the lock (a
            # commit must not wait for it); no later chain can donate it
            (idx,) = np.nonzero(np.asarray(newly))
            if idx.size == 0:
                return None
            idx = idx.astype(np.int32)
            if self._tenant_id_of_device is not None:
                tenant_ids = np.asarray(
                    self._tenant_id_of_device(idx), np.int32)
            else:
                tenant_ids = np.zeros(idx.size, np.int32)
            self._m_reported.inc(int(idx.size))
            return state_change_columns(idx, tenant_ids, now_s)

    def warm_presence_programs(self) -> None:
        """Compile (or load from the cache) what a served sweep runs on
        the packed epoch — the sweep itself and the merge a step makes
        when a sweep overtook it — on the live carry, dropping the
        outputs: nothing is adopted, so nothing changes.  Called at
        start so the first sweep in service compiles nothing."""
        with self._lock:
            # dispatched under the lock: a single step may donate the
            # epoch the moment the lock is free
            packed = self.current_packed
            # [1]: the swept carry is dropped at once, so the warm-up
            # never holds more than the epoch and one copy of it
            newly = _packed_sweep()(packed, jnp.int32(0), jnp.int32(0))[1]
            merged = _merge_presence(packed.rows, packed.rows, newly)
        jax.block_until_ready(merged)

    # -- queries (reference: DeviceStateImpl RPCs) --------------------------

    def get_device_state(self, device_token: str) -> Dict[str, object]:
        """Last-known state for one device, as a host dict."""
        device_id = self.identity.device.lookup(device_token)
        require(
            device_id != NULL_ID, EntityNotFound(f"no device {device_token!r}")
        )
        return self.get_device_state_by_id(int(device_id))

    def get_device_state_by_id(self, device_id: int) -> Dict[str, object]:
        with self._lock:
            s = self.current
        require(
            0 <= device_id < s.capacity, EntityNotFound(f"bad device id {device_id}")
        )
        # one batched device→host transfer for the whole row
        r = jax.device_get(jax.tree.map(lambda a: a[device_id], s))
        row = {
            "device_id": device_id,
            "last_event_ts_s": int(r.last_event_ts_s),
            "last_event_type": int(r.last_event_type),
            "presence_missing": bool(r.presence_missing),
            "last_location": {
                "lat": float(r.last_lat),
                "lon": float(r.last_lon),
                "elevation": float(r.last_elevation),
                "ts_s": int(r.last_location_ts_s),
            },
            "last_alert": {
                "code": int(r.last_alert_code),
                "ts_s": int(r.last_alert_ts_s),
            },
            "last_values": np.asarray(r.last_values).tolist(),
            "last_value_ts_s": np.asarray(r.last_value_ts_s).tolist(),
        }
        if row["last_event_type"] == NULL_ID:
            row["last_event_type"] = None
        return row

    # -- migration (ownership handoff; rpc/migration.py) --------------------

    def export_row(self, device_id: int) -> Dict[str, object]:
        """One device's FULL state row as a jsonable dict (unlike
        :meth:`get_device_state_by_id`'s REST subset, this carries every
        field, plus shape metadata so the importer can check fit)."""
        with self._lock:
            s = self.current
        require(0 <= device_id < s.capacity,
                EntityNotFound(f"bad device id {device_id}"))
        row = jax.device_get(jax.tree.map(lambda a: a[device_id], s))
        out: Dict[str, object] = {
            "_mtype_slots": s.num_mtype_slots,
            "_ewma_scales": s.num_ewma_scales,
        }
        for fld in s.__dataclass_fields__:
            v = np.asarray(getattr(row, fld))
            out[fld] = v.tolist() if v.ndim else v.item()
        return out

    def import_row(self, device_id: int, row: Dict[str, object]) -> bool:
        """Adopt an exported row, NEWEST-WINS: applied only when the
        incoming ``last_event_ts_s`` is newer than what this host holds
        (a device that already re-registered and streamed here must not
        be rolled back).  Measurement-shape mismatches drop the per-slot
        stats but keep the scalar columns.  Returns True if applied."""
        with self._lock:
            s = self.current
            require(0 <= device_id < s.capacity,
                    EntityNotFound(f"bad device id {device_id}"))
            incoming = int(row.get("last_event_ts_s") or 0)
            current_ts = int(np.asarray(s.last_event_ts_s[device_id]))
            if incoming <= current_ts:
                return False
            shapes_ok = (int(row.get("_mtype_slots") or 0) ==
                         s.num_mtype_slots
                         and int(row.get("_ewma_scales") or 0) ==
                         s.num_ewma_scales)
            updates = {}
            for fld in s.__dataclass_fields__:
                if fld not in row:
                    continue
                cur = getattr(s, fld)
                if cur.ndim > 1 and not shapes_ok:
                    continue
                val = jnp.asarray(np.asarray(row[fld], cur.dtype))
                if val.shape != cur.shape[1:]:
                    continue
                updates[fld] = cur.at[device_id].set(val)
            self._state = s.replace(**updates)
            self._packed = None
        return True

    def missing_device_ids(self) -> List[int]:
        """Devices currently flagged missing (vectorized scan + index copy).

        The lock covers only the epoch snapshot; the blocking
        device→host transfer runs OUTSIDE it (epochs are immutable —
        commit replaces, never mutates).  A REST scan must never hold
        the lease lock through a D2H round-trip: ``commit_packed`` takes
        this lock on every batch, so a slow transfer here would stall
        dispatch (swlint lock-discipline LK004)."""
        with self._lock:
            s = self.current
        mask = np.asarray(s.presence_missing)
        return [int(i) for i in np.nonzero(mask)[0]]

    def missing_device_tokens(self) -> List[str]:
        """Missing devices as TOKENS — the cross-host-safe form (dense
        ids are meaningful only inside their minting host's identity
        map, so the remote facade surfaces this, never the id form)."""
        return [t for t in (self.identity.device.token_of(i)
                            for i in self.missing_device_ids())
                if t is not None]

    def seen_since_tokens(self, since_s: int) -> List[str]:
        """Token form of :meth:`seen_since` (see missing_device_tokens)."""
        return [t for t in (self.identity.device.token_of(i)
                            for i in self.seen_since(since_s))
                if t is not None]

    def seen_since(self, since_s: int) -> List[int]:
        """Devices with any event at/after ``since_s``.  Snapshot under
        the lock, compute + transfer outside it (see
        :meth:`missing_device_ids`)."""
        with self._lock:
            s = self.current
        mask = np.asarray(
            (s.last_event_type != NULL_ID) & (s.last_event_ts_s >= since_s)
        )
        return [int(i) for i in np.nonzero(mask)[0]]

    def summary(self) -> Dict[str, int]:
        # snapshot under the lock, transfer outside it (see
        # missing_device_ids — the lease lock must never ride a D2H)
        with self._lock:
            s = self.current
        has = np.asarray(s.last_event_type != NULL_ID)
        missing = np.asarray(s.presence_missing)
        return {
            "devices_with_state": int(has.sum()),
            "devices_missing": int(missing.sum()),
        }

"""Overlapped host pipeline: decode pool ordering, egress offload, and
the stage-overlap acceptance proof (stubbed slow step).

The tentpole claim: with the host loop split into overlapped stages, the
only work left on the critical dispatch thread is batch assembly + step
launch — decode (window N+1) and egress (window N-1) run concurrently
with the device step of window N.  The proof here uses a stubbed slow
step and slow egress sink: wall clock stays near N×step while the
per-stage timers (``pipeline.stage_*_s``) show the full egress cost was
paid — their totals exceed wall elapsed, which is only possible when
the stages overlap.
"""

import threading
import time

import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.ingest.batcher import Batcher
from sitewhere_tpu.ingest.sources import DecodePool, InboundEventSource
from sitewhere_tpu.pipeline.packed import METRIC_SCALARS
from sitewhere_tpu.runtime import faults
from sitewhere_tpu.runtime.dispatcher import PipelineDispatcher
from sitewhere_tpu.runtime.metrics import MetricsRegistry

from time_limit import WAIT_S, wait_until

WIDTH = 8


# ---------------------------------------------------------------------------
# decode pool: parallel decode, ordered delivery
# ---------------------------------------------------------------------------

class TestDecodePool:
    def test_parallel_decode_delivers_in_submission_order(self):
        pool = DecodePool(workers=4, max_pending=64)
        try:
            delivered = []
            done = threading.Event()
            n = 12

            def work(i):
                # later jobs finish FIRST (reverse sleep) — only the
                # ordered-delivery lane keeps the output in order
                time.sleep(0.002 * (n - i))
                return i

            def deliver(result, exc):
                assert exc is None
                delivered.append(result)
                if len(delivered) == n:
                    done.set()

            t0 = time.perf_counter()
            for i in range(n):
                pool.submit("src", lambda i=i: work(i), deliver)
            assert done.wait(10.0)
            wall = time.perf_counter() - t0
            assert delivered == list(range(n))
            # 4 workers: wall must beat the serial sum (overlap proof)
            serial = sum(0.002 * (n - i) for i in range(n))
            assert wall < serial
        finally:
            pool.stop()

    def test_independent_keys_do_not_serialize(self):
        pool = DecodePool(workers=2, max_pending=64)
        try:
            got = []
            evt = threading.Event()

            def deliver(result, exc):
                got.append(result)
                if len(got) == 2:
                    evt.set()

            # "a" blocks until "b" has started: deliverable only if the
            # two keys decode concurrently (serialized lanes would leave
            # "a" waiting out the timeout and return the failure marker)
            b_started = threading.Event()
            pool.submit(
                "a", lambda: "a" if b_started.wait(5.0) else "a-stalled",
                deliver)
            pool.submit("b", lambda: b_started.set() or "b", deliver)
            assert evt.wait(10.0)
            assert sorted(got) == ["a", "b"]
        finally:
            pool.stop()

    def test_decode_error_routes_to_deliver_in_order(self):
        pool = DecodePool(workers=2, max_pending=8)
        try:
            seen = []
            done = threading.Event()

            def deliver(result, exc):
                seen.append((result, type(exc).__name__ if exc else None))
                if len(seen) == 3:
                    done.set()

            def boom():
                raise ValueError("bad payload")

            pool.submit("k", lambda: 1, deliver)
            pool.submit("k", boom, deliver)
            pool.submit("k", lambda: 3, deliver)
            assert done.wait(5.0)
            assert seen == [(1, None), (None, "ValueError"), (3, None)]
        finally:
            pool.stop()

    def test_submit_backpressure_blocks_at_max_pending(self):
        pool = DecodePool(workers=1, max_pending=2)
        try:
            release = threading.Event()
            pool.submit("k", lambda: release.wait(10), lambda r, e: None)
            pool.submit("k", lambda: None, lambda r, e: None)
            # budget exhausted: the third submit must block until a slot
            # frees — the receiver-thread backpressure contract
            unblocked = threading.Event()

            def third():
                pool.submit("k", lambda: None, lambda r, e: None)
                unblocked.set()

            t = threading.Thread(target=third, daemon=True)
            t.start()
            assert not unblocked.wait(0.15)
            release.set()
            assert unblocked.wait(5.0)
            assert pool.flush(5.0)
        finally:
            release.set()
            pool.stop()

    def test_stopped_pool_degrades_to_synchronous(self):
        pool = DecodePool(workers=1, max_pending=2)
        pool.stop()
        got = []
        pool.submit("k", lambda: 41, lambda r, e: got.append((r, e)))
        assert got == [(41, None)]

    def test_deliver_raising_base_exception_does_not_kill_worker(self):
        pool = DecodePool(workers=1, max_pending=8)
        try:
            got = []
            done = threading.Event()

            def bad_deliver(result, exc):
                raise SystemExit(3)  # a deliver re-raising a decode-stage
                # BaseException must not end the worker thread

            pool.submit("k", lambda: 1, bad_deliver)
            pool.submit("k", lambda: 2,
                        lambda r, e: (got.append(r), done.set()))
            assert done.wait(5.0)
            assert got == [2]
            assert pool.delivery_errors == 1
        finally:
            pool.stop()


# ---------------------------------------------------------------------------
# dispatcher fixture with a stubbed (slow) step
# ---------------------------------------------------------------------------

def _fake_step_out(bi):
    """A packed step's ``(oi, metrics)`` accepting every valid row."""
    valid = (np.asarray(bi)[0] != 0).astype(np.int32)
    oi = np.zeros((10, WIDTH), np.int32)
    oi[0] = valid  # flags row: F_ACCEPTED for every valid row
    mets = np.zeros(len(METRIC_SCALARS) + 6, np.int32)
    mets[0] = mets[1] = int(valid.sum())  # processed / accepted
    return oi, mets


class FakeStateManager:
    current = None
    current_packed = None

    def commit(self, new_state, present_now=None):
        pass

    def commit_packed(self, new_packed, present_now=None,
                      read_epoch=None, lease_token=None):
        pass

    def lease_packed(self):
        return None, None

    def step_packed(self, step, tables, bi, bf, place=None):
        return step(tables, self.current_packed, bi, bf)


class SlowStore:
    """Event-store stand-in whose append costs ``delay_s`` host time."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.rows = 0
        self.batches = 0
        self.append_threads = set()
        self.first_ids = []  # first device_id of each appended batch
        # (egress-order probe for the ring's ordering barrier)

    def append_columns(self, cols, mask=None):
        self.append_threads.add(threading.current_thread().name)
        if self.delay_s:
            time.sleep(self.delay_s)
        self.rows += int(mask.sum()) if mask is not None \
            else len(cols["device_id"])
        self.batches += 1
        self.first_ids.append(int(np.asarray(cols["device_id"])[0]))

    def flush(self):
        pass


def make_dispatcher(step_s=0.0, egress_s=0.0, egress_offload=True,
                    inflight_depth=1, deadline_ms=60_000.0, store=None,
                    **kw):
    metrics = MetricsRegistry()
    batcher = Batcher(
        width=WIDTH, n_shards=1, registry_capacity=64,
        resolve_device=lambda t: NULL_ID, resolve_mtype=lambda n: 0,
        resolve_alert=lambda n: 0, deadline_ms=deadline_ms,
        metrics=metrics)
    store = store if store is not None else SlowStore(egress_s)
    disp = PipelineDispatcher(
        batcher=batcher,
        registry_provider=lambda: None,
        state_manager=FakeStateManager(),
        rules_provider=lambda: None,
        zones_provider=lambda: None,
        event_store=store,
        inflight_depth=inflight_depth,
        egress_offload=egress_offload,
        metrics=metrics,
        **kw,
    )

    def slow_step(tables, ps, bi, bf):
        if step_s:
            time.sleep(step_s)  # the stubbed "device step"
        oi, mets = _fake_step_out(bi)
        return ps, oi, mets, np.zeros(64, bool)

    disp._tables_packed = lambda: None
    disp._packed_step = slow_step
    return disp, store, metrics


def ingest_window(disp):
    disp.ingest_arrays(device_id=np.arange(WIDTH, dtype=np.int32))


def make_ring_dispatcher(ring_depth=2, egress_s=0.0, egress_offload=True,
                         **kw):
    """Dispatcher on the device-resident ring path with a STUBBED chain:
    plans from a real batcher, a fake K-step chain whose
    stacked outputs accept every row, and no real jax dispatch — the
    ring's windowing/commit/ordering semantics in isolation."""
    metrics = MetricsRegistry()
    batcher = Batcher(
        width=WIDTH, n_shards=1, registry_capacity=64,
        resolve_device=lambda t: NULL_ID, resolve_mtype=lambda n: 0,
        resolve_alert=lambda n: 0, deadline_ms=60_000.0)
    store = SlowStore(egress_s)
    disp = PipelineDispatcher(
        batcher=batcher,
        registry_provider=lambda: None,
        state_manager=FakeStateManager(),
        rules_provider=lambda: None,
        zones_provider=lambda: None,
        event_store=store,
        egress_offload=egress_offload,
        ring_depth=ring_depth,
        metrics=metrics,
        **kw,
    )
    disp._tables_packed = lambda: None
    chain_calls = []

    def fake_chain(tables, ps, *slots):
        k = len(slots) // 2
        chain_calls.append(k)
        outs = [_fake_step_out(slots[i]) for i in range(k)]
        return (ps, np.stack([o for o, _ in outs]),
                np.stack([m for _, m in outs]), np.zeros(64, bool))

    def fake_packed_step(tables, ps, bi, bf):
        oi, mets = _fake_step_out(bi)
        return ps, oi, mets, np.zeros(64, bool)

    for k in range(1, ring_depth + 1):
        disp._ring_chains[k] = fake_chain
    disp._packed_step = fake_packed_step
    disp._chain_calls = chain_calls
    return disp, store, metrics


# ---------------------------------------------------------------------------
# egress offload semantics
# ---------------------------------------------------------------------------

class TestEgressOffload:
    def test_flush_drains_the_offload_queue(self):
        disp, store, _ = make_dispatcher(egress_s=0.01)
        disp.start()
        try:
            for _ in range(4):
                ingest_window(disp)
            disp.flush()
            # flush's contract: every row ingested BEFORE the call has
            # completed egress on return — offloaded or not
            assert store.rows == 4 * WIDTH
            assert not disp._inflight
            with disp._lock:
                assert disp._plans_outstanding == 0
        finally:
            disp.stop()

    def test_egress_runs_off_the_dispatch_thread(self):
        disp, store, _ = make_dispatcher(egress_s=0.0)
        disp.start()
        try:
            ingest_window(disp)
            disp.flush()
            assert store.rows == WIDTH
            # offloaded: the append ran on the supervised egress worker,
            # not on this (ingesting) thread and not on the loop thread
            assert all("egress" in t for t in store.append_threads)
        finally:
            disp.stop()

    def test_offload_disabled_is_inline_and_needs_no_threads(self):
        disp, store, _ = make_dispatcher(egress_offload=False)
        # no start(): the inline path must work exactly as before
        ingest_window(disp)
        disp.flush()
        assert store.rows == WIDTH
        assert all("egress" not in t for t in store.append_threads)

    def test_unstarted_dispatcher_degrades_to_inline(self):
        disp, store, _ = make_dispatcher(egress_offload=True)
        ingest_window(disp)
        disp.flush()
        assert store.rows == WIDTH

    def test_backpressure_bounds_the_window(self):
        disp, store, _ = make_dispatcher(egress_s=0.05, inflight_depth=1)
        disp.start()
        try:
            for _ in range(6):
                ingest_window(disp)
                # the dispatch side may run ahead of egress by at most
                # the bounded window (queued) + one in-progress item
                assert len(disp._inflight) <= disp.egress_queue_depth
            disp.flush()
            assert store.rows == 6 * WIDTH
        finally:
            disp.stop()

    def test_egress_crash_fails_closed_and_worker_recovers(self):
        """An egress fault kills the WORKER mid-window: its supervisor
        restarts the loop, sibling plans still drain, and the dead
        plan's accounting keeps the commit gate closed forever (the
        at-least-once rule: never commit past an un-egressed plan)."""
        faults.clear()
        disp, store, _ = make_dispatcher(egress_s=0.0)
        disp.start()
        try:
            faults.inject("dispatcher.egress", times=1)
            ingest_window(disp)           # this plan's egress dies
            assert wait_until(lambda: faults.fired("dispatcher.egress") == 1)
            ingest_window(disp)           # sibling must still egress
            disp.flush()                  # ends once the sibling landed
            assert store.rows == WIDTH    # only the sibling landed
            assert disp.egress_failures == 1
            assert wait_until(lambda: disp._egress_super.restarts >= 1)
            assert not disp._egress_super.escalated
            with disp._lock:
                # the dead plan is still outstanding: gate failed closed
                assert disp._plans_outstanding == disp._plans_failed == 1
        finally:
            faults.clear()
            disp.stop()

    def test_a_raise_after_the_release_leaves_flush_waiting_for_live_plans(
            self):
        """A plan whose egress raised after it left the outstanding count
        (here, in its flight record) is no plan for flush to skip: a later
        flush still waits for a plan another thread is stepping."""

        class Recorder:
            def record(self, **rec):
                pass

            def anomaly(self, kind, detail=""):
                pass

        disp, store, _ = make_dispatcher(step_s=0.5)
        disp.flightrec = Recorder()
        raised = []

        def flight_record(plan, out, replay_depth, commit, **kw):
            if commit == "ok" and not raised:
                raised.append(plan.seq)
                raise RuntimeError("flight record failed")

        disp._flight_record = flight_record
        disp.start()
        try:
            ingest_window(disp)
            disp.flush()
            assert raised and disp.egress_failures == 1
            assert store.rows == WIDTH
            with disp._lock:
                assert disp._plans_outstanding == disp._plans_failed == 0
            # the live plan: outstanding from its take, then stepping
            # (0.5 s) on another thread, in neither the batcher nor the
            # egress window
            t = threading.Thread(target=ingest_window, args=(disp,))
            t.start()
            assert wait_until(lambda: disp._plans_outstanding == 1)
            disp.flush()
            assert store.rows == 2 * WIDTH
            t.join(timeout=WAIT_S)
            assert not t.is_alive()
        finally:
            disp.stop()


# ---------------------------------------------------------------------------
# device-resident dispatch ring: multi-step in-flight semantics
# ---------------------------------------------------------------------------

def ingest_window_at(disp, base):
    """One full-width fill window with device ids base..base+WIDTH-1
    (distinguishable in the store's egress-order probe)."""
    disp.ingest_arrays(
        device_id=(base + np.arange(WIDTH)).astype(np.int32))


class TestDeviceResidentRing:
    def test_full_windows_chain_k_steps_one_sync_per_chain(self):
        disp, store, metrics = make_ring_dispatcher(ring_depth=2)
        disp.start()
        try:
            for i in range(4):
                ingest_window_at(disp, i * WIDTH % 64)
            disp.flush()
            assert store.rows == 4 * WIDTH
            # first call is the boot-time warm-up (all-invalid ring)
            assert disp._chain_calls == [2, 2, 2]
            # the whole point: ONE blocking host sync per K-step chain
            assert metrics.counter("pipeline.host_syncs").value == 2
            assert metrics.counter("pipeline.ring_chains").value == 2
            assert not disp._ring
            with disp._lock:
                assert disp._plans_outstanding == 0
        finally:
            disp.stop()

    def test_flush_drains_partial_ring_no_lost_commits(self):
        disp, store, metrics = make_ring_dispatcher(ring_depth=2)
        disp.start()
        try:
            for i in range(3):   # one chain + one plan stranded in ring
                ingest_window_at(disp, i * WIDTH)
            disp.flush()
            # flush's contract holds through the ring: every row
            # ingested before the call completed egress on return
            assert store.rows == 3 * WIDTH
            assert not disp._ring
            with disp._lock:
                assert disp._plans_outstanding == 0
            assert metrics.counter("pipeline.ring_flushes").value == 1
        finally:
            disp.stop()

    def test_stop_drains_ring(self):
        disp, store, _ = make_ring_dispatcher(ring_depth=4)
        disp.start()
        ingest_window_at(disp, 0)   # sits in the ring, chain never fills
        disp.stop()                 # shutdown flush must not strand it
        assert store.rows == WIDTH
        with disp._lock:
            assert disp._plans_outstanding == 0

    def test_non_ring_plan_drains_ring_first_in_order(self):
        """A deadline/flush partial must not overtake ring-held
        predecessors: per-device event order across plans is preserved
        by the ordering barrier (ring drains single-step first)."""
        disp, store, _ = make_ring_dispatcher(ring_depth=3)
        disp.start()
        try:
            ingest_window_at(disp, 0)    # ring slot 0
            ingest_window_at(disp, 8)    # ring slot 1 (chain needs 3)
            disp.ingest_arrays(
                device_id=np.full(4, 16, np.int32))  # partial, pending
            disp.flush()                 # emits the partial (reason=flush)
            assert store.rows == 2 * WIDTH + 4
            assert store.first_ids == [0, 8, 16]
        finally:
            disp.stop()

    def test_barrier_drains_only_predecessors_by_seq(self):
        """The ordering barrier is seq-bounded: ring plans emitted AFTER
        the non-ring plan are successors — draining them would reorder
        them ahead of it (and starve it under sustained fill traffic)."""
        disp, store, _ = make_ring_dispatcher(ring_depth=4)
        disp.start()
        try:
            ingest_window_at(disp, 0)    # seq 0 → ring
            ingest_window_at(disp, 8)    # seq 1 → ring
            disp.ingest_arrays(device_id=np.full(4, 16, np.int32))
            partial = disp._take(disp.batcher.flush)[0]   # seq 2
            ingest_window_at(disp, 24)   # seq 3 → ring (a successor)
            disp._run_plan(partial)
            # predecessors stepped, then the partial; successor stays
            with disp._step_lock:
                assert [p.seq for p in disp._ring] == [3]
            disp.flush()
            assert store.first_ids == [0, 8, 16, 24]
            assert store.rows == 3 * WIDTH + 4
        finally:
            disp.stop()

    def test_egress_crash_mid_ring_fails_closed_on_dead_step_only(self):
        """An egress fault on slot 0 of a chained dispatch kills the
        worker; the supervisor restarts it, slot 1 still drains, and
        ONLY the dead step stays outstanding — the commit gate fails
        closed on exactly the uncommitted slice of the ring."""
        faults.clear()
        disp, store, _ = make_ring_dispatcher(ring_depth=2)
        disp.start()
        try:
            faults.inject("dispatcher.egress", times=1)
            ingest_window_at(disp, 0)
            ingest_window_at(disp, 8)   # chain of 2 dispatches here
            assert wait_until(lambda: faults.fired("dispatcher.egress") == 1)
            disp.flush()                        # ends once slot 1 landed
            assert store.rows == WIDTH          # only the sibling landed
            assert disp.egress_failures == 1
            assert wait_until(lambda: disp._egress_super.restarts >= 1)
            assert not disp._egress_super.escalated
            with disp._lock:
                assert disp._plans_outstanding == disp._plans_failed == 1
        finally:
            faults.clear()
            disp.stop()

    def test_overload_signal_reflects_oldest_ring_plan(self):
        """The seal-lag watermark must see plans buffered for a chain:
        with steps in flight beyond the windowed FIFO, the signal is the
        age of the OLDEST in-flight batch, not the last fetched one."""
        disp, _, _ = make_ring_dispatcher(ring_depth=4)
        # no start(): plans stay in the ring (no loop thread to age them
        # out), which is exactly the wedged state the signal must see
        disp.steps = 1  # past the warm-up gate
        ingest_window_at(disp, 0)
        ingest_window_at(disp, 8)
        assert len(disp._ring) == 2
        time.sleep(0.05)
        assert disp.oldest_unsealed_wait_s() >= 0.04
        disp._flush_ring()

    def test_ring_ineligible_plans_take_the_single_step_path(self):
        """Re-injected (replay-depth) plans and deadline partials never
        wait in the ring."""
        disp, store, _ = make_ring_dispatcher(ring_depth=2)
        # depth > 0 == egress-worker context: must dispatch immediately
        plan = disp._take(lambda: disp.batcher.add_arrays(
            device_id=np.arange(WIDTH, dtype=np.int32)))[0]
        assert not disp._ring_eligible(plan, replay_depth=1)
        assert disp._ring_eligible(plan, replay_depth=0)
        disp._run_plan(plan, replay_depth=1)
        assert not disp._ring   # never waited for a chain
        disp.flush()
        assert store.rows == WIDTH


# ---------------------------------------------------------------------------
# start_host_copy: only the deleted-buffer race is silent
# ---------------------------------------------------------------------------

class _FakeDeviceArray:
    def __init__(self, exc=None):
        self.exc = exc
        self.calls = 0

    def copy_to_host_async(self):
        self.calls += 1
        if self.exc is not None:
            raise self.exc


class TestStartHostCopy:
    def test_deleted_buffer_race_stays_silent(self):
        from sitewhere_tpu.pipeline import packed

        before = packed.host_copy_errors
        errors = []
        packed.start_host_copy(
            _FakeDeviceArray(RuntimeError("Array has been deleted.")),
            on_error=errors.append)
        assert packed.host_copy_errors == before
        assert errors == []

    def test_unexpected_error_is_counted_and_does_not_stop_siblings(self):
        from sitewhere_tpu.pipeline import packed

        before = packed.host_copy_errors
        errors = []
        ok = _FakeDeviceArray()
        packed.start_host_copy(
            _FakeDeviceArray(RuntimeError("transfer engine wedged")),
            ok, on_error=errors.append)
        assert packed.host_copy_errors == before + 1
        assert len(errors) == 1
        # the failure must not abort the remaining arrays' copies
        # (the old bare guard returned on ANY error)
        assert ok.calls == 1

    def test_host_arrays_are_skipped(self):
        from sitewhere_tpu.pipeline import packed

        before = packed.host_copy_errors
        packed.start_host_copy(np.zeros(4), object())
        assert packed.host_copy_errors == before


# ---------------------------------------------------------------------------
# tier-1 CPU smoke: the ring end-to-end through a real Instance
# ---------------------------------------------------------------------------

class TestRingEndToEnd:
    def test_forced_ring_runs_journal_to_egress_on_cpu(self, tmp_path):
        """The device-resident dispatch loop exercised on EVERY tier-1
        run, not only on TPU: a real Instance with forced ``ring_depth=2``
        drives NDJSON wire payloads journal→dispatch(chained)→egress, and
        the host-sync counter proves the amortization (1 blocking sync
        per 2-step chain)."""
        import json as _json

        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.runtime.config import Config

        width = 64
        inst = Instance(Config({
            "instance": {"id": "ring-smoke",
                         "data_dir": str(tmp_path / "data")},
            "pipeline": {"width": width, "registry_capacity": 128,
                         "mtype_slots": 4, "deadline_ms": 60_000.0,
                         "n_shards": 1, "ring_depth": 2},
            "presence": {"scan_interval_s": 3600.0,
                         "missing_after_s": 1800},
        }, apply_env=False))
        inst.start()
        try:
            inst.device_management.create_device_type(
                token="sensor", name="Sensor")
            for i in range(width):
                inst.device_management.create_device(
                    token=f"d-{i}", device_type="sensor")
                inst.device_management.create_device_assignment(
                    device=f"d-{i}")

            def payload(r):
                return "\n".join(_json.dumps({
                    "deviceToken": f"d-{i}", "type": "Measurement",
                    "request": {"name": "temp", "value": 1.0 + i,
                                "eventDate": 1_753_800_000 + r},
                }) for i in range(width)).encode()

            for r in range(4):
                inst.dispatcher.ingest_wire_lines(payload(r))
            inst.dispatcher.flush()
            snap = inst.dispatcher.metrics_snapshot()
            assert snap["ring_depth"] == 2
            assert snap["ring_chains"] == 2          # 4 steps, 2 chains
            assert snap["accepted"] == 4 * width     # no lost commits
            # host syncs amortized to 1 per K steps (the tentpole claim)
            assert snap["host_syncs"] == 2
            assert snap["steps"] == 4
            # egress really landed (journal→dispatch→egress, not a stub)
            inst.event_store.flush()
            assert inst.event_store.total_events == 4 * width
            # chained commits merged state correctly
            row = inst.device_state.get_device_state("d-5")
            assert row["last_event_ts_s"] == 1_753_800_003
            # commit gate advanced past every journaled record
            assert inst.dispatcher.journal_reader.committed == 4
        finally:
            inst.stop()
            inst.terminate()


# ---------------------------------------------------------------------------
# the overlap acceptance proof
# ---------------------------------------------------------------------------

class TestHostpathBenchSmoke:
    def test_tool_reports_every_stage(self, tmp_path):
        """tools/hostpath_bench.py must run end-to-end and report a
        positive per-stage breakdown (tier-1 smoke: the tool is how a
        stage regression localizes)."""
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "tools", "hostpath_bench.py")
        spec = importlib.util.spec_from_file_location("hostpath_bench", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        r = mod.run(width=128, iters=2, capacity=1024, ring_k=2,
                    data_dir=str(tmp_path))
        for key in ("decode_s", "batch_s", "dispatch_s", "egress_s",
                    "h2d_stage_s", "d2h_fetch_s", "host_rtt_s",
                    "seal_s", "serial_s", "pipeline_bound_s",
                    "seal_perceived_s", "seal_background_s"):
            assert r[key] > 0.0, key
        # ISSUE 13 acceptance: the hot path's perceived seal cost is a
        # packed row copy + enqueue (segment writes run on the worker
        # pool, attributed to their own background stage timer)
        assert r["seal_background_segments"] > 0
        assert r["seal_perceived_s"] < r["seal_s"]
        # dwell is RTT-clamped: ≥ 0, and positive wherever the chain
        # outruns the trivial-program probe (every real backend)
        assert r["device_dwell_s"] >= 0.0
        assert r["ring_chain_k"] == 2
        assert r["host_syncs_per_batch_ring"] == 0.5
        assert r["pipeline_bound_s"] <= r["serial_s"]
        assert r["overlapped_events_per_s"] >= r["serial_events_per_s"]
        # ISSUE 9 acceptance: the always-on flight recorder's per-batch
        # record cost stays under 1% of the throughput-bounding stage
        assert r["flightrec_record_s"] > 0.0
        assert r["flightrec_overhead_frac"] < 0.01
        # ISSUE 17 acceptance: per-tenant usage attribution rides the
        # same bar — the per-plan ledger charge (bucket→tenant resolve +
        # sketch/window fold) stays under 1% of the bounding stage
        assert r["metering_charge_s"] > 0.0
        assert r["metering_overhead_frac"] < 0.01
        # ISSUE 10 acceptance: the decode A/B + bytes-copied columns are
        # recorded, and with the native toolchain the fill-direct path
        # copies ZERO bytes per event (3x-fewer bar trivially cleared)
        for key in ("decode_fill_s", "decode_native_s", "decode_python_s",
                    "decode_speedup_fill_vs_native",
                    "bytes_copied_per_event_native_total",
                    "bytes_copied_per_event_fill_total"):
            assert key in r, key
        from sitewhere_tpu.native import load_swwire
        if load_swwire() is not None:
            assert r["fill_direct"] is True
            assert r["bytes_copied_per_event_fill_total"] == 0.0
            assert r["bytes_copied_per_event_native_total"] > 0.0
            assert r["bytes_copied_3x"] is True
            assert r["ingest_fill_s"] > 0.0


class TestFillDirectEndToEnd:
    def test_fill_path_runs_wire_to_egress_with_zero_copies(self, tmp_path):
        """Tier-1 fill-direct smoke: a real Instance (native build
        forced by the module-level skip in test_native_fill; here we
        just require it) ingests full-width NDJSON payloads through the
        zero-copy path — decode writes straight into adopted packed
        buffers — and the bytes-copied counters prove it: zero decode
        bytes, zero batch bytes, all rows accepted and egressed."""
        import json as _json

        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.native import load_swwire
        from sitewhere_tpu.runtime.config import Config

        if load_swwire() is None:
            pytest.skip("native toolchain unavailable")
        width = 64
        inst = Instance(Config({
            "instance": {"id": "fill-smoke",
                         "data_dir": str(tmp_path / "data")},
            "pipeline": {"width": width, "registry_capacity": 128,
                         "mtype_slots": 4, "deadline_ms": 60_000.0,
                         "n_shards": 1},
            "presence": {"scan_interval_s": 3600.0,
                         "missing_after_s": 1800},
        }, apply_env=False))
        inst.start()
        try:
            dm = inst.device_management
            dm.create_device_type(token="sensor", name="Sensor")
            for i in range(width):
                dm.create_device(token=f"d-{i}", device_type="sensor")
                dm.create_device_assignment(device=f"d-{i}")

            def payload(r):
                return "\n".join(_json.dumps({
                    "deviceToken": f"d-{i}", "type": "Measurement",
                    "request": {"name": "temp", "value": 1.0 + i,
                                "eventDate": 1_753_800_000 + r},
                }) for i in range(width)).encode()

            for r in range(3):
                n = inst.dispatcher.ingest_wire_lines(payload(r))
                assert n == width
            inst.dispatcher.flush()
            snap = inst.dispatcher.metrics_snapshot()
            assert snap["accepted"] == 3 * width
            reg = inst.metrics
            # the zero-copy proof: the hot path materialized NOTHING
            assert reg.counter("pipeline.bytes_copied.decode").value == 0
            assert reg.counter("pipeline.bytes_copied.batch").value == 0
            inst.event_store.flush()
            assert inst.event_store.total_events == 3 * width
            # journal carries the payloads (replayability unchanged)
            assert inst.ingest_journal.end_offset == 3
            # A/B: the same wire bytes through the classic path land the
            # same rows, with nonzero copies — the counters discriminate
            inst.dispatcher._fill_enabled = False
            assert inst.dispatcher.ingest_wire_lines(payload(3)) == width
            inst.dispatcher.flush()
            assert reg.counter("pipeline.bytes_copied.decode").value > 0
            snap = inst.dispatcher.metrics_snapshot()
            assert snap["accepted"] == 4 * width
        finally:
            inst.stop()
            inst.terminate()

    def test_fill_path_through_decode_pool_source(self, tmp_path):
        """The pooled wire lane: reservations are filled on decode-pool
        workers and committed in delivery order — per-source ordering
        and the journal offset↔row correspondence survive."""
        import json as _json

        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.native import load_swwire
        from sitewhere_tpu.runtime.config import Config

        if load_swwire() is None:
            pytest.skip("native toolchain unavailable")
        width = 32
        inst = Instance(Config({
            "instance": {"id": "fill-pool",
                         "data_dir": str(tmp_path / "data")},
            "pipeline": {"width": width, "registry_capacity": 128,
                         "mtype_slots": 4, "deadline_ms": 60_000.0,
                         "n_shards": 1},
            "ingest": {"decode_workers": 2},
            "presence": {"scan_interval_s": 3600.0,
                         "missing_after_s": 1800},
        }, apply_env=False))
        inst.start()
        try:
            dm = inst.device_management
            dm.create_device_type(token="sensor", name="Sensor")
            for i in range(width):
                dm.create_device(token=f"d-{i}", device_type="sensor")
                dm.create_device_assignment(device=f"d-{i}")
            src = InboundEventSource("pool-wire", [], decoder=lambda b: [],
                                     raw_wire=True)
            src.decode_pool = inst.decode_pool
            src.on_wire_payload = lambda p, s: \
                inst.dispatcher.ingest_wire_lines(p, source_id=s)
            src.on_wire_decode = inst.dispatcher.decode_wire_lines
            src.on_wire_decoded = inst.dispatcher.ingest_wire_decoded

            def payload(r):
                return "\n".join(_json.dumps({
                    "deviceToken": f"d-{i}", "type": "Measurement",
                    "request": {"name": "temp", "value": float(r),
                                "eventDate": 1_753_800_000 + r},
                }) for i in range(width)).encode()

            for r in range(4):
                src.on_encoded_payload(payload(r))
            assert inst.decode_pool.flush(5.0)
            inst.dispatcher.flush()
            snap = inst.dispatcher.metrics_snapshot()
            assert snap["accepted"] == 4 * width
            assert inst.metrics.counter(
                "pipeline.bytes_copied.decode").value == 0
            # delivery order held: the last value committed per device
            # is the LAST payload's
            row = inst.device_state.get_device_state("d-3")
            assert row["last_event_ts_s"] == 1_753_800_003
        finally:
            inst.stop()
            inst.terminate()


class TestStageOverlap:
    def test_host_step_p50_below_2x_device_step_and_stages_overlap(self):
        """Acceptance: with fault injection off, host_step p50 drops
        below 2× device_step — egress demonstrably overlaps the stubbed
        slow step (stage timers sum past wall clock)."""
        assert not faults.active()
        step_s, egress_s, n = 0.05, 0.04, 5
        disp, store, metrics = make_dispatcher(
            step_s=step_s, egress_s=egress_s)
        disp.start()
        try:
            # warm the numpy→jax conversion in batch emission: the
            # first call initializes the backend (~100ms) and would
            # otherwise be charged to the measured window
            ingest_window(disp)
            disp.flush()
            dispatch = metrics.timer("pipeline.stage_dispatch_s")
            egress = metrics.timer("pipeline.stage_egress_s")
            d_total0, e_total0 = dispatch.total, egress.total

            t0 = time.perf_counter()
            for _ in range(n):
                ingest_window(disp)
            disp.flush()
            wall = time.perf_counter() - t0
            assert store.rows == (n + 1) * WIDTH

            # host_step (the per-plan time the dispatch thread spends) ≈
            # the device step alone, NOT step + egress: below 2× device
            assert dispatch.count == n + 1
            assert dispatch.percentile(0.5) < 2 * step_s

            # the egress cost was actually paid — just elsewhere
            e_spent = egress.total - e_total0
            assert egress.count == n + 1
            assert e_spent >= n * egress_s * 0.9

            # serial execution would need ≥ n*(step+egress); the
            # pipeline finished well under it, and the stages' summed
            # host time exceeds wall clock — only possible overlapped.
            # (margin absorbs scheduler noise on a loaded CI machine)
            serial = n * (step_s + egress_s)
            assert wall < serial * 0.9
            assert (dispatch.total - d_total0) + e_spent > wall * 0.9
        finally:
            disp.stop()


# ---------------------------------------------------------------------------
# the width ladder (PR 33): every rung compiled at start(), partial plans
# stepped at the width they need
# ---------------------------------------------------------------------------

LADDER_W = 1024          # rungs 128 / 256 / 1,024
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = []           # one entry a program XLA compiled, process-wide


def _count_compiles():
    """Register (once) the listener ``benchmarks/deployment.py``
    counts compiles with; returns the running list."""
    import jax.monitoring as monitoring

    if not getattr(_count_compiles, "on", False):
        monitoring.register_event_duration_secs_listener(
            lambda event, s, **kw: _compiles.append(event)
            if event == _COMPILE_EVENT else None)
        _count_compiles.on = True
    return _compiles


def _ladder_instance(tmp_path, ring_depth, **pipeline):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    inst = Instance(Config({
        "instance": {"id": "ladder", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": LADDER_W, "registry_capacity": 256,
                     "mtype_slots": 4, "deadline_ms": 60_000.0,
                     "n_shards": 1, "ring_depth": ring_depth, **pipeline},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
    }, apply_env=False))
    inst.start()
    inst.device_management.create_device_type(token="sensor", name="S")
    for i in range(8):
        inst.device_management.create_device(token=f"d-{i}",
                                             device_type="sensor")
        inst.device_management.create_device_assignment(device=f"d-{i}")
    return inst


@pytest.fixture(scope="module", params=[0, 2], ids=["no-ring", "ring2"])
def ladder(request, tmp_path_factory):
    """A started instance (ring off, ring on) past its first full-width
    plan, which takes every first-use program that is not a step —
    what the benchmark's priming does."""
    compiles = _count_compiles()
    inst = _ladder_instance(tmp_path_factory.mktemp("ladder"), request.param)
    d = inst.dispatcher
    assert d.warm_error is None and d.ring_depth == request.param
    d.ingest_arrays(device_id=np.arange(LADDER_W, dtype=np.int32) % 8,
                    mtype_id=np.zeros(LADDER_W, np.int32),
                    event_type=np.zeros(LADDER_W, np.int32),
                    ts_s=np.full(LADDER_W, 1_754_000_000, np.int32))
    d.flush()
    inst.device_state.current   # the checkpoint's reader, as the harness
    yield inst, compiles
    inst.stop()
    inst.terminate()


@pytest.mark.parametrize("n, rung", [(1, 128), (128, 128), (129, 256),
                                     (256, 256), (257, 1024), (1000, 1024)])
def test_live_partial_plan_of_every_rung_compiles_nothing(ladder, n, rung):
    inst, compiles = ladder
    d = inst.dispatcher
    narrow0 = inst.metrics.counter("pipeline.steps_narrow").value
    steps0, accepted0, before = d.steps, d.totals["accepted"], len(compiles)
    d.ingest_arrays(device_id=np.arange(n, dtype=np.int32) % 8,
                    mtype_id=np.zeros(n, np.int32),
                    event_type=np.zeros(n, np.int32),
                    ts_s=np.full(n, 1_754_000_100, np.int32),
                    value=np.ones(n, np.float32))
    d.flush()
    assert d.totals["accepted"] - accepted0 == n
    assert d.steps - steps0 == 1
    assert len(compiles) == before, compiles[before:]
    rec = inst.flightrec.recent(1)[0]
    assert (rec["rows"], rec["width"], rec["reason"]) == (n, rung, "flush")
    assert rec["fill"] == round(n / LADDER_W, 4)   # of pipeline.width
    # counted as narrow unless it stepped the configured width
    assert (inst.metrics.counter("pipeline.steps_narrow").value - narrow0
            == (1 if rung < LADDER_W else 0))


def test_ring_refuses_a_rung_full_deadline_plan():
    """Full means a full ``pipeline.width``: a deadline plan that fills
    its narrow rung is a latency-carrying partial, not ring traffic."""
    from sitewhere_tpu.ingest.batcher import BatchPlan

    disp, _, _ = make_ring_dispatcher(ring_depth=2)
    t = [0.0]
    disp.batcher = Batcher(
        width=LADDER_W, n_shards=1, registry_capacity=64,
        resolve_device=lambda tok: NULL_ID, resolve_mtype=lambda n: 0,
        resolve_alert=lambda n: 0, deadline_ms=5.0, clock=lambda: t[0])
    disp.batcher.add_arrays(device_id=np.zeros(128, np.int32))
    t[0] = 1.0
    plan = disp.batcher.poll()
    assert (plan.reason, plan.n_events, plan.width) == ("deadline", 128, 128)
    assert not disp._ring_eligible(plan, replay_depth=0)
    # nor would the reason alone admit it
    forged = BatchPlan(n_events=128, width=128, full_width=LADDER_W,
                       reason="fill", seq=7)
    assert not disp._ring_eligible(forged, replay_depth=0)
    (full,) = disp.batcher.add_arrays(device_id=np.zeros(LADDER_W, np.int32))
    assert full.width == LADDER_W and disp._ring_eligible(full, 0)


def test_step_failure_on_a_narrow_plan_bisects_and_dead_letters(tmp_path):
    import json as _json

    inst = _ladder_instance(tmp_path, 0, deadline_ms=5.0)
    try:
        lines = "\n".join(_json.dumps({
            "deviceToken": "d-0", "type": "Measurement",
            "request": {"name": "temp", "value": v,
                        "eventDate": 1_754_600_000 + i},
        }) for i, v in enumerate(
            [1.0, float("nan"), 3.0, float("nan"), 5.0])).encode()
        faults.device_inject("device.dispatch", times=None,
                             when_nonfinite=True)
        inst.dispatcher.ingest_wire_lines(lines)
        inst.dispatcher.flush()
        faults.device_clear()
        inst.event_store.flush()
        assert inst.event_store.total_events == 3
        letters = [d for d in inst.list_dead_letters(limit=10)
                   if d.get("kind") == "device-poison"]
        assert sum(d["count"] for d in letters) == 2
        c = inst.metrics.snapshot()["counters"]
        assert c["device.fault.step_faults"] == 1
        assert c["device.fault.poison_rows"] == 2
        # the failed plan and every clean subset rode the 128-row rung
        widths = {r["width"] for r in inst.flightrec.recent(50)}
        assert widths == {128}
        assert c["pipeline.steps_narrow"] == c["pipeline.steps"] >= 1
    finally:
        faults.device_clear()
        inst.stop()
        inst.terminate()


# ---------------------------------------------------------------------------
# a wire payload that finds the pipeline empty leaves at once
# ---------------------------------------------------------------------------

def _wire_payload(n, ts_s=1_754_700_000):
    import json as _json

    return "\n".join(_json.dumps({
        "deviceToken": f"d-{i}", "type": "Measurement",
        "request": {"name": "temp", "value": float(i), "eventDate": ts_s},
    }) for i in range(n)).encode()


class GatedStore(SlowStore):
    """An event store whose appends wait for ``gate``: the plan being
    egressed stays outstanding until the test opens it."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()

    def append_columns(self, cols, mask=None):
        self.gate.wait(WAIT_S)
        super().append_columns(cols, mask)


def _idle_rows(metrics):
    return metrics.counter("ingest.rows_emitted_idle").value


def test_a_wire_payload_into_an_idle_pipeline_leaves_at_once():
    """A 10 s deadline, and the payload is a plan before the call
    returns: emitted, counted outstanding (its egress is held), no row
    left pending."""
    store = GatedStore()
    disp, _, metrics = make_dispatcher(deadline_ms=10_000.0, store=store)
    disp.start()
    try:
        assert disp.ingest_wire_lines(_wire_payload(3)) == 3
        assert disp.batcher.pending == 0
        assert disp._plans_outstanding == 1
        assert disp.batcher.emitted_batches == 1
        assert _idle_rows(metrics) == 3
    finally:
        store.gate.set()
        disp.stop()
    assert store.rows == 3 and store.batches == 1


def test_a_payload_behind_an_outstanding_plan_waits_for_the_deadline():
    store = GatedStore()
    disp, _, metrics = make_dispatcher(deadline_ms=1_000.0, store=store)
    disp.start()
    try:
        disp.ingest_wire_lines(_wire_payload(3))
        assert disp._plans_outstanding == 1
        disp.ingest_wire_lines(_wire_payload(2))
        # the first plan is outstanding: the second payload coalesces
        assert disp.batcher.pending == 2
        assert _idle_rows(metrics) == 3
        store.gate.set()
        # the loop's deadline poll takes it, not the intake
        wait_until(lambda: disp.batcher.pending == 0
                   and disp._plans_outstanding == 0)
        assert disp.batcher.emitted_batches == 2
        assert _idle_rows(metrics) == 3
        assert metrics.counter("ingest.rows_emitted").value == 5
    finally:
        store.gate.set()
        disp.stop()
    assert store.rows == 5 and store.batches == 2


def test_a_payload_behind_pending_rows_waits():
    disp, store, metrics = make_dispatcher(deadline_ms=10_000.0)
    disp.start()
    try:
        disp.ingest_arrays(device_id=np.zeros(1, np.int32))
        disp.ingest_wire_lines(_wire_payload(3))
        assert disp.batcher.pending == 4
        assert disp.batcher.emitted_batches == 0
        assert _idle_rows(metrics) == 0
        disp.flush()
    finally:
        disp.stop()
    assert store.rows == 4 and store.batches == 1


def test_journal_replay_emits_no_idle_plan(tmp_path):
    """Replay shares the live intake's column path and keeps coalescing
    under the deadline: its rows leave on the drain's flush."""
    inst = _ladder_instance(tmp_path, 0)
    try:
        d = inst.dispatcher
        assert d.ingest_wire_lines(_wire_payload(5)) == 5
        assert d.batcher.pending == 0
        d.flush()
        assert _idle_rows(inst.metrics) == 5
        assert d.replay_journal(from_offset=0) == 5
        assert _idle_rows(inst.metrics) == 5
        assert inst.metrics.counter("ingest.rows_emitted").value == 10
        assert d.totals["accepted"] == 10
    finally:
        inst.stop()
        inst.terminate()


def test_ring_refuses_an_idle_plan():
    from sitewhere_tpu.ingest.batcher import BatchPlan

    disp, _, _ = make_ring_dispatcher(ring_depth=2)
    disp.batcher.add_arrays(device_id=np.zeros(WIDTH - 1, np.int32))
    plan = disp.batcher.emit_idle()
    assert plan.reason == "idle" and plan.n_events == WIDTH - 1
    assert not disp._ring_eligible(plan, replay_depth=0)
    full = BatchPlan(n_events=WIDTH, width=WIDTH, reason="idle", seq=3)
    assert not disp._ring_eligible(full, replay_depth=0)

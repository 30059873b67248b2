"""One run of one cell: deployment up, traffic from the seed, a priming
pass, the measured window, the drain, the comparisons that decide
``correct`` (the configuration's kind makes those with its plain
reference, ``_compare`` those that hold whatever the deployment), and
the run's record for the metric readers.

The client's side is the yardstick.  A priority ``CallbackConnector``
sees every row the system stored (``_egress`` appends to the store
before it submits to outbound), bins the rows that are a send's own
(the kind says which) by the stamp their send carried and notes the
time.  An event's latency is that time minus the time its send was DUE;
an event refused, lost or still undelivered when the final drain ends
is failed and takes the drain's end as its time.
"""

from __future__ import annotations

import faulthandler
import os
import threading
import time

import numpy as np

from benchmarks import cells, trace_reduce
from benchmarks.deployment import (CompileMeter, Deployment, device_doc,
                                   memory_peak_bytes)

UNSENT, OK, SHED, PARTIAL = 0, 1, 2, 3
OVERLOAD_SAMPLE_S = 0.1
RUN_TAIL_LIMIT_S = 200.0     # after the window: drain, checks, close


def enable_compile_cache() -> str:
    """JAX's persistent cache where the program keeps it (the checkout's
    ``.jax_cache`` unless JAX_COMPILATION_CACHE_DIR places it), taking
    every program however fast it compiled: a later run of the same cell
    must compile nothing.  Returns the directory."""
    import jax

    from sitewhere_tpu.runtime import compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return compile_cache.enable_compile_cache()


class SendLog:
    """One row per send, written by the thread that makes it."""

    def __init__(self, capacity: int) -> None:
        self.body = np.zeros(capacity, np.int32)
        self.n = np.zeros(capacity, np.int64)
        self.due = np.zeros(capacity, np.float64)
        self.sent = np.zeros(capacity, np.float64)
        self.done = np.zeros(capacity, np.float64)
        self.status = np.zeros(capacity, np.int8)
        self.measured = np.zeros(capacity, bool)

    def planned(self, seq: int, body: int, n: int, due: float,
                measured: bool) -> None:
        """A send that is due; it stays UNSENT (failed) until made."""
        self.body[seq], self.n[seq], self.due[seq] = body, n, due
        self.measured[seq] = measured

    def used(self) -> np.ndarray:
        return np.nonzero(self.n > 0)[0]


class DeliveryLog:
    """The client's connector: rows delivered, binned by send."""

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation

        self._span = TraceAnnotation
        self.seq_of = None            # until bind(): no send was made
        self.own_rows = None
        self.delivered = np.zeros(0, np.int64)
        self.rows: list = []          # (time, seqs, counts)
        self.stray = 0                # source rows with no send's stamp
        self.cond = threading.Condition()

    def bind(self, capacity: int, seq_of, own_rows) -> None:
        """Size the log for ``capacity`` sends whose stamps ``seq_of``
        turns back into sequence numbers; ``own_rows(cols)`` marks the
        delivered rows that are a send's own."""
        self.delivered = np.zeros(capacity, np.int64)
        self.own_rows = own_rows
        self.seq_of = seq_of

    def __call__(self, cols, mask) -> None:
        if self.seq_of is None:       # no traffic yet: a warm-up row
            return
        with self._span("bench.connector"):
            now = time.perf_counter()
            keep = np.asarray(mask) & self.own_rows(cols)
            seq = self.seq_of(np.asarray(cols["ts_s"])[keep],
                              np.asarray(cols["ts_ns"])[keep])
            ok = (seq >= 0) & (seq < len(self.delivered))
            counts = np.bincount(seq[ok])
            seqs = np.nonzero(counts)[0]
            with self.cond:
                self.stray += int((~ok).sum())
                self.rows.append((now, seqs, counts[seqs]))
                self.delivered[seqs] += counts[seqs]
                self.cond.notify_all()

    def wait(self, seq: int, n: int, timeout_s: float) -> bool:
        """Until all ``n`` rows of send ``seq`` were delivered."""
        deadline = time.perf_counter() + timeout_s
        with self.cond:
            while self.delivered[seq] < n:
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self.cond.wait(left)
        return True


class Client:
    """What a traffic kind sends through."""

    def __init__(self, dep: Deployment, sends: SendLog,
                 delivery: DeliveryLog) -> None:
        from jax.profiler import TraceAnnotation

        from sitewhere_tpu.runtime.overload import OverloadShed

        self.dep, self.sends, self.delivery = dep, sends, delivery
        self._span, self._shed = TraceAnnotation, OverloadShed

    def send(self, seq: int, body: int, n: int, due: float, call,
             measured: bool) -> None:
        """Make send ``seq`` (``call()`` hands it to the system and
        returns the rows accepted, or None) and log it."""
        log = self.sends
        log.planned(seq, body, n, due, measured)
        log.sent[seq] = time.perf_counter()
        try:
            with self._span("bench.send"):
                got = call()
            status = OK if got is None or got == n else PARTIAL
        except self._shed:
            status = SHED
        log.done[seq] = time.perf_counter()
        log.status[seq] = status


class Run:
    """The record of one run, as the metric readers take it."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)
        self._lat = None

    def counter(self, name: str) -> float:
        """A counter's growth over the window."""
        return self.marks1.get(name, 0) - self.marks0.get(name, 0)

    def timer(self, name: str) -> tuple:
        """(seconds, observations) a timer gained over the window."""
        s0, c0 = self.marks0.get(name, (0.0, 0))
        s1, c1 = self.marks1.get(name, (0.0, 0))
        return s1 - s0, c1 - c0

    def dispatcher(self, key: str) -> float:
        """Growth of one ``metrics_snapshot()`` count over the window."""
        return (self.marks1["_dispatcher"].get(key, 0)
                - self.marks0["_dispatcher"].get(key, 0))

    def delivered_in_window(self) -> int:
        return int(sum(c[self.sends.measured[s]].sum()
                       for t, s, c in self.delivery.rows
                       if self.t_begin <= t <= self.t_end))

    def delivery_times(self) -> np.ndarray:
        return np.asarray([t for t, s, c in self.delivery.rows
                           if self.t_begin <= t <= self.t_end and len(s)])

    def event_latencies(self) -> tuple:
        """(latency seconds, events) over the measured sends: delivered
        rows at their delivery time, every other row at the end of the
        final drain."""
        if self._lat is None:
            log = self.sends
            lat, wt = [], []
            for t, seqs, counts in self.delivery.rows:
                m = log.measured[seqs]
                lat.append(t - log.due[seqs[m]])
                wt.append(counts[m])
            seqs = np.nonzero(log.measured)[0]
            missing = log.n[seqs] - np.minimum(self.delivery.delivered[seqs],
                                               log.n[seqs])
            lat.append(self.t_final - log.due[seqs[missing > 0]])
            wt.append(missing[missing > 0])
            self._lat = (np.concatenate(lat), np.concatenate(wt))
        return self._lat

    def latency_percentile_ms(self, q: float):
        lat, wt = self.event_latencies()
        if not wt.sum():
            return None
        order = np.argsort(lat)
        cum = np.cumsum(wt[order])
        at = np.searchsorted(cum, q / 100.0 * cum[-1])
        return float(lat[order][min(at, len(lat) - 1)] * 1e3)


class Checks:
    """Every comparison that decides ``correct``, printed as made."""

    def __init__(self, log) -> None:
        self.failed: list = []
        self.compared: dict = {}      # name -> [number, its limit]
        self.log = log

    def check(self, name: str, ok, detail: str = "", got=None,
              limit=1) -> None:
        """A yes-or-no comparison is the number 1 or 0 against 1."""
        self.log(f"  [{'ok' if ok else 'FAIL'}] {name}"
                 + (f": {detail}" if detail else ""))
        self.compared[name] = [int(bool(ok)) if got is None else got, limit]
        if not ok:
            self.failed.append(name)

    def equal(self, name: str, got, want) -> None:
        """Exact: the limit is the reference's number, the gap 0."""
        self.check(name, got == want, f"got {got}, want {want}", got, want)


def _window_watch(dep, meter, seconds, t_begin, trace_dir, trace_s, out):
    """The harness's own thread during the window: every 100 ms it
    samples the overload state and the checkpointer's generation
    (``out["checkpoints"]``: when each periodic checkpoint ended, in
    seconds into the window); in a traced run it profiles the window's
    last ``trace_s`` seconds; when the window ends it reads the
    counters."""
    import jax

    t_end = t_begin + seconds
    trace_at = t_end - trace_s if trace_dir else None
    span = None
    checkpointer = dep.inst.checkpointer
    generation = checkpointer.generation
    states = out["states"] = []
    saves = out["checkpoints"] = []
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if trace_at is not None and span is None and now >= trace_at:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            span.__enter__()
        if now >= t_begin:
            states.append(int(dep.inst.overload.state))
            if checkpointer.generation != generation:
                generation = checkpointer.generation
                saves.append(now - t_begin)
        time.sleep(max(0.0, min(OVERLOAD_SAMPLE_S, t_end - now)))
    out["marks1"] = dep.marks()
    out["in_window"] = meter.take()
    if span is not None:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True, log=print,
             on_run=None):
    """Run ``cell`` (as ``cells.resolve_cell`` returns it) once.
    Returns the contract's result object, or None when the machine does
    not hold the chips the cell asks for.  ``on_run(run)`` sees the
    run's record before the deployment closes (the sweep, the tests)."""
    from jax.profiler import TraceAnnotation

    device = device_doc()
    log(f"platform: {device['platform']}  kind: {device['kind']}  "
        f"count: {device['count']}  cell: {cell['name']} seed={seed} "
        f"seconds={seconds} trace={int(trace)}")
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell["chips"]):
        log(f"refusing: {cell['name']} needs {cell['chips']} TPU chip(s)")
        return None

    from sitewhere_tpu import native

    meter = CompileMeter()
    mod = native.build_swwire()
    log(f"[native] built {os.path.basename(mod.__file__)}")

    config, params = cell["config"], cell["traffic"]
    kind = cells.load_module(params["kind_file"])
    delivery = DeliveryLog()
    dep = Deployment(config, delivery, log=log)
    try:
        dep.populate()
        log(f"[setup] compile so far: {meter.take()}")
        traffic = kind.build(params, dep, np.random.default_rng(seed))
        capacity = int(traffic.max_sends(seconds))
        sends = SendLog(capacity)
        delivery.bind(capacity, traffic.seq_of, dep.kind.own_rows)
        client = Client(dep, sends, delivery)

        with TraceAnnotation("bench.prime"):
            traffic.prime(client)
            dep.drain()
            # the periodic checkpoint reads the state through a program
            # of its own (unpack_state); the first one falls inside the
            # window, so that program is taken through here
            dep.inst.device_state.current
        log(f"[setup] primed with {len(sends.used())} sends; compile: "
            f"{meter.take()}; overload {dep.inst.overload.state.name}")

        trace_dir = os.path.join(dep.tmp, "trace") if trace else None
        trace_s = min(float(params.get("trace_seconds", 4.0)), seconds / 2)
        # A run that cannot finish says where it stands and fails; it
        # does not hang its caller (window + drain + checks + close).
        faulthandler.dump_traceback_later(seconds + RUN_TAIL_LIMIT_S,
                                          exit=True)
        seen: dict = {}
        marks0 = dep.marks()
        meter.take()
        t_begin = time.perf_counter() + 0.05
        watch = threading.Thread(
            target=_window_watch, name="bench-watch",
            args=(dep, meter, seconds, t_begin, trace_dir, trace_s, seen))
        watch.start()
        traffic.run(client, t_begin, seconds)
        watch.join()
        t_end = t_begin + seconds
        marks1, in_window, states = (seen["marks1"], seen["in_window"],
                                     seen["states"])
        with TraceAnnotation("bench.drain"):
            dep.drain()
        t_final = time.perf_counter()
        log(f"[window] {seconds}s, drained {t_final - t_end:.2f}s after; "
            f"compile inside the window: {in_window}; checkpoints ended "
            f"{[round(t, 1) for t in seen['checkpoints']]} s into it"
            + (f", traced from {seconds - trace_s:.1f} s" if trace else ""))

        run = Run(cell=cell["name"], config=config, traffic=params,
                  seconds=float(seconds), t_begin=t_begin, t_end=t_end,
                  t_final=t_final, sends=sends, delivery=delivery,
                  marks0=marks0, marks1=marks1,
                  overload_states=np.asarray(states, np.int64),
                  width=dep.width, capacity=dep.capacity,
                  n_shards=dep.n_shards, mtype_slots=dep.mtype_slots,
                  ring_depth=dep.ring_depth, device=device,
                  setup_s=t_begin - t_process, trace=None,
                  checkpoints=seen["checkpoints"],
                  trace_from=seconds - trace_s if trace else None,
                  memory_peak_bytes=memory_peak_bytes())
        if trace:
            run.trace = trace_reduce.reduce_dir(
                trace_dir, config.get("programs", {}), dep.ring_depth)

        checks = Checks(log)
        _compare(checks, dep, traffic, run, in_window)
        measured = sends.measured
        attempted = int(sends.n[measured].sum())
        done = int(np.minimum(delivery.delivered, sends.n)[measured].sum())
        result = {"correct": not checks.failed, "attempted": attempted,
                  "failed": attempted - done, "metrics": {},
                  "device": dict(device,
                                 memory_peak_bytes=run.memory_peak_bytes)}
        for entry, path in cell["per_layer" if trace else "end_to_end"]:
            value = cells.load_module(path).read(run)
            if value is not None:
                result["metrics"][entry["name"]] = {
                    "value": float(value), "unit": entry["unit"]}
        if run.trace:
            result["device"].update(busy_s=run.trace["busy_s"],
                                    window_s=run.trace["window_s"])
            result["breakdown"] = {
                "device_ops": run.trace["device_ops"],
                "idle_gaps": run.trace["idle_gaps"]}
        status = np.bincount(sends.status[measured], minlength=4)
        log(f"[result] sends ok/shed/partial/unsent: {status[OK]}/"
            f"{status[SHED]}/{status[PARTIAL]}/{status[UNSENT]}; events "
            f"attempted {attempted}, undelivered {attempted - done}; "
            f"overload left NORMAL in {int((run.overload_states > 0).sum())}"
            f" of {len(states)} samples; watchdog "
            f"{marks1['_dispatcher']['device_fault']['watchdog']}")
        if checks.failed:
            log(f"[result] {len(checks.failed)} check(s) failed: "
                f"{checks.failed}")
        if on_run is not None:
            on_run(run)
        # last key: every number compared beside its limit
        result["compared"] = checks.compared
        return result
    finally:
        dep.close()
        faulthandler.cancel_dump_traceback_later()


def _compare(checks: Checks, dep: Deployment, traffic, run: Run,
             in_window: dict) -> None:
    """The kind's comparisons with its reference, then what holds
    whatever the deployment (no kind can switch these off): the default
    rung, nothing quarantined, lost or copied by mistake, nothing shed
    but by admission, nothing compiled inside the window."""
    from sitewhere_tpu import native
    from sitewhere_tpu.pipeline import packed

    sends, inst = run.sends, dep.inst
    # rows its reference expects refused, each held to the reference by
    # a comparison of the kind's own, are dead letters that no send shed
    # by admission explains
    refused = dep.kind.compare(checks, dep, traffic, run) or 0

    fault = dep.d.metrics_snapshot()["device_fault"]
    checks.check("breaker at 'chained' with zero trips",
                 fault["breaker"]["levelName"] == "chained"
                 and fault["breaker"]["trips"] == 0, str(fault["breaker"]))
    checks.equal("quarantined devices", fault["quarantined_devices"], 0)
    checks.equal("egress failures", dep.d.egress_failures, 0)
    checks.equal("host_copy_errors",
                 packed.host_copy_errors - dep.copy_errors0, 0)
    checks.equal("native.build_fallbacks", native.build_fallbacks, 0)
    dep.kind.compare_intake(checks, dep, traffic, run)
    checks.equal("dead letters = sends shed by admission",
                 int(inst.dead_letters.end_offset) - refused,
                 int((sends.status == SHED).sum()))
    checks.equal("programs compiled inside the window",
                 in_window["programs"], 0)
    if dep.n_shards > 1:
        placed = len(inst.device_state.current.last_event_ts_s
                     .sharding.device_set)
        checks.equal("state sharded over chips", placed, dep.n_shards)

"""Benchmarks: device events/sec/chip through the TPU pipeline (+ aux configs).

One in-process run per invocation::

    python bench.py --config N          # on the chip (default N = 1)
    JAX_PLATFORMS=cpu python bench.py --config N   # reduced CPU profile

A config that finds no TPU fails (exit 1) unless the caller asked for
the CPU with ``JAX_PLATFORMS=cpu``; it then runs the reduced profile and
says ``"backend": "cpu"``.  A CPU number is never a device metric.  The
last stdout line is the config's JSON doc {"metric", "value", "unit",
"vs_baseline", "backend", ...extras}; config 6 prints one provisional
line per mesh scale before it.

These are the round-5 configs as they stand — the benchmark the driver
can run per cell (open-loop rates, a ``workloads`` table, trace
reduction) is ROADMAP S0.

Baseline target (BASELINE.md): 1M events/sec/chip end-to-end with <10ms p99,
so ``vs_baseline = events_per_sec / 1e6`` and the headline JSON also carries
``device_step_ms`` / ``host_step_p50_ms`` / ``host_step_p99_ms``.

Configs (BASELINE.md):
  1 (default)  headline fused-pipeline events/sec/chip + per-step latency
  2            dispatcher path: sources -> batcher -> step -> store/outbound
  3            windowed anomaly-detection analytics job
  4            8-tenant fan-out + presence sweep (multi-tenant demux)
  5            streaming-media append + QR label render (host mixed workload)
  6            mesh-fused ring weak-scaling sweep over the visible devices

Accounting (config 1): 8 distinct host-generated batches are staged to the
device once, then the measured loop cycles through them — every step runs
the fused pipeline step (validation, enrichment, threshold rules, geofence,
state update, derived alerts, metrics) on a batch it has not seen in 8
steps.  Staging is excluded; the dispatcher-path number (config 2) covers
the host edge.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

TARGET_EVENTS_PER_SEC = 1e6  # BASELINE.md north star, per chip


def asked_for_cpu() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_backend() -> str:
    """``"tpu"``, or ``"cpu"`` when the caller set ``JAX_PLATFORMS=cpu``
    (the reduced profile); any other outcome ends the run with exit 1 —
    a benchmark that finds no chip does not fall back."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu" or (backend == "cpu" and asked_for_cpu()):
        return backend
    sys.exit(f"bench: no TPU (backend {backend!r}); set JAX_PLATFORMS=cpu "
             f"to run the reduced CPU profile")


# ---------------------------------------------------------------------------
# shared workload builders
# ---------------------------------------------------------------------------

def build_tables(capacity: int, n_active: int, n_tenants: int = 1,
                 n_zones: int = 1):
    import jax.numpy as jnp

    from sitewhere_tpu.ops.geo import pad_polygon
    from sitewhere_tpu.schema import (
        AssignmentStatus,
        DeviceState,
        Registry,
        RuleTable,
        ZoneTable,
    )

    idx = jnp.arange(capacity)
    on = idx < n_active
    registry = Registry.empty(capacity).replace(
        active=on,
        tenant_id=jnp.where(on, idx % n_tenants, -1),
        device_type_id=jnp.where(on, 0, -1),
        assignment_id=jnp.where(on, idx, -1),
        assignment_status=jnp.where(on, AssignmentStatus.ACTIVE, 0),
        area_id=jnp.where(on, 1, -1),
        customer_id=jnp.where(on, 2, -1),
        asset_id=jnp.where(on, 3, -1),
    )
    state = DeviceState.empty(capacity)
    rules = RuleTable.empty(64)
    rules = rules.replace(
        active=rules.active.at[0].set(True),
        mtype_id=rules.mtype_id.at[0].set(0),
        op=rules.op.at[0].set(0),
        threshold=rules.threshold.at[0].set(90.0),
        alert_code=rules.alert_code.at[0].set(7),
    )
    zones = ZoneTable.empty(64, max_verts=16)
    for z in range(n_zones):
        lo, hi = z * 2.0, z * 2.0 + 10.0
        padded = pad_polygon([[lo, lo], [hi, lo], [hi, hi], [lo, hi]], 16)
        zones = zones.replace(
            active=zones.active.at[z].set(True),
            verts=zones.verts.at[z].set(jnp.asarray(padded)),
            nvert=zones.nvert.at[z].set(4),
            alert_code=zones.alert_code.at[z].set(9),
        )
    return registry, state, rules, zones


def host_batches(width: int, n_active: int, n_batches: int,
                 n_tenants: int = 1):
    """Pre-generate distinct host-side (numpy) event batches."""
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(n_batches):
        device_id = rng.integers(0, n_active, width).astype(np.int32)
        batches.append(
            dict(
                valid=np.ones(width, bool),
                device_id=device_id,
                tenant_id=(device_id % n_tenants).astype(np.int32),
                event_type=(rng.random(width) < 0.5).astype(np.int32),
                ts_s=np.full(width, 1_753_800_000, np.int32),
                ts_ns=rng.integers(0, 1_000_000_000, width).astype(np.int32),
                mtype_id=np.zeros(width, np.int32),
                value=rng.uniform(0, 100, width).astype(np.float32),
                lat=rng.uniform(-20, 20, width).astype(np.float32),
                lon=rng.uniform(-20, 20, width).astype(np.float32),
                elevation=np.zeros(width, np.float32),
                alert_code=np.full(width, -1, np.int32),
                alert_level=np.zeros(width, np.int32),
                command_id=np.full(width, -1, np.int32),
                payload_ref=np.arange(width, dtype=np.int32),
                update_state=np.ones(width, bool),
            )
        )
    return batches


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


# ---------------------------------------------------------------------------
# config 1: headline fused pipeline step (throughput + latency)
# ---------------------------------------------------------------------------

def measure_rtt(samples: int = 5) -> float:
    """Median dispatch round-trip of a trivial jitted program (seconds).
    The shared probe from the telemetry library, so bench evidence and
    the production device.stage_ms calibration subtract the SAME floor."""
    from sitewhere_tpu.pipeline.telemetry import measure_rtt as probe

    return probe(samples)


def packed_chain(tables, staged, chain_k: int):
    """K packed steps chained in ONE compiled program cycling the staged
    batches (phase-C device-latency methodology): one host round-trip
    covers K steps, and the returned acc folds a reduction over every
    output leg so XLA cannot dead-code-eliminate the work.  Shared by
    config 1's phase C and tools/width_sweep.py so the sweep always
    measures exactly what the bench measures."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.pipeline.packed import packed_pipeline_step

    stacked_i = jnp.stack([b for b, _ in staged])
    stacked_f = jnp.stack([f for _, f in staged])
    n = len(staged)

    @jax.jit
    def chain(c):
        def body(i, cr):
            c, acc = cr
            k = i % n
            bi = jax.lax.dynamic_index_in_dim(stacked_i, k, keepdims=False)
            bf = jax.lax.dynamic_index_in_dim(stacked_f, k, keepdims=False)
            c, oi, metrics, present = packed_pipeline_step(tables, c, bi, bf)
            acc = acc + metrics.sum() + oi.sum() + present.sum()
            return c, acc
        return jax.lax.fori_loop(0, chain_k, body, (c, jnp.int32(0)))

    return chain


def bench_pipeline() -> None:
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.pipeline.packed import (
        pack_batch_host,
        pack_state,
        pack_tables,
        packed_pipeline_step,
    )

    from sitewhere_tpu.pipeline import pipeline_step
    from sitewhere_tpu.pipeline.packed import packed_step_default
    from sitewhere_tpu.schema import EventBatch

    reduced = require_backend() == "cpu"
    capacity, n_active = 16384, 10000
    width = 16_384 if reduced else 131_072
    iters = 10 if reduced else 40
    lat_iters = 10 if reduced else 24
    chain_k = 16 if reduced else 256
    registry, state, rules, zones = build_tables(capacity, n_active)
    raw = host_batches(width, n_active, n_batches=8)

    # PURE-step interface choice (backend-adaptive; pipeline/packed.py):
    # on TPU the packed form (11 buffers/call instead of ~110) removes
    # the per-call dispatch tax; for a bare CPU step the repack memcpys
    # make per-column faster.  The shipped DISPATCHER defaults packed on
    # every backend — config 2 measures that path as deployed.
    use_packed = packed_step_default()
    if use_packed:
        tables = jax.jit(pack_tables)(registry, rules, zones)
        carry = jax.jit(pack_state)(state)
        step = jax.jit(packed_pipeline_step, donate_argnums=(1,))
        staged = [
            tuple(jax.device_put(a) for a in pack_batch_host(b, width))
            for b in raw
        ]

        def run(c, i):
            c, oi, metrics, present = step(tables, c, *staged[i % len(staged)])
            return c, metrics

        def force(metrics):
            return int(metrics[0])  # processed
    else:
        carry = state
        step = jax.jit(pipeline_step, donate_argnums=(1,))
        staged = [
            EventBatch(**{k: jax.device_put(v) for k, v in b.items()})
            for b in raw
        ]

        def run(c, i):
            c, out = step(registry, c, rules, zones, staged[i % len(staged)])
            return c, out

        def force(out):
            return int(out.metrics.processed)

    jax.block_until_ready(staged)

    # Warm-up: compile (fetch so compile can't bleed into the timed region).
    carry, out = run(carry, 0)
    force(out)

    # Timing boundaries are device-to-host scalar FETCHES: the last
    # step's metrics depend on the donated state chain, so one fetch
    # forces every dispatched step.

    # Phase A: async throughput (the deployment steady state — dispatch
    # ahead, fetch at the end; the fetch is inside the timed region).
    t0 = time.perf_counter()
    for i in range(iters):
        carry, out = run(carry, i)
    processed = force(out)  # forces the whole chain
    t1 = time.perf_counter()
    assert processed == width
    events_per_sec = width * iters / (t1 - t0)

    # Phase B: host-observed per-step latency (fetch each step); phase C
    # below measures the device-side step latency.
    times = []
    for i in range(lat_iters):
        t2 = time.perf_counter()
        carry, out = run(carry, i)
        force(out)
        times.append(time.perf_counter() - t2)
    p50 = float(np.percentile(times, 50) * 1e3)
    p99 = float(np.percentile(times, 99) * 1e3)

    # Phase C: device-side step latency — chain K steps inside ONE compiled
    # program (fori_loop cycling the 8 staged batches) so exactly one host
    # round-trip covers K steps; subtract the round-trip measured on a
    # trivial program.  This is the per-step number a host-attached chip
    # sees, and the one the <10ms p99 target is judged against (an event's
    # end-to-end latency = batcher deadline + this + egress).  The carry
    # folds in a reduction over EVERY output leg so XLA cannot
    # dead-code-eliminate the rule/geofence/enrichment work.
    if use_packed:
        chain = packed_chain(tables, staged, chain_k)
    else:
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *staged)

        @jax.jit
        def chain(c):
            def body(i, cr):
                c, acc = cr
                batch = jax.tree.map(
                    lambda x: jax.lax.dynamic_index_in_dim(
                        x, i % len(staged), keepdims=False), stacked)
                c, out = pipeline_step(registry, c, rules, zones, batch)
                acc = (acc
                       + out.metrics.accepted
                       + out.metrics.threshold_alerts
                       + out.metrics.zone_alerts
                       + out.rule_id.sum() + out.zone_id.sum()
                       + out.assignment_id.sum()
                       + out.derived_alerts.alert_code.sum())
                return c, acc
            return jax.lax.fori_loop(0, chain_k, body, (c, jnp.int32(0)))

    rtt = measure_rtt()

    carry, probe = chain(carry)  # compile
    int(probe)
    t5 = time.perf_counter()
    carry, probe = chain(carry)
    int(probe)
    t6 = time.perf_counter()
    device_step_ms = max(0.0, (t6 - t5 - rtt)) / chain_k * 1e3

    emit({
        "metric": "pipeline_events_per_sec_per_chip",
        "value": round(events_per_sec, 1),
        "unit": "events/s",
        "vs_baseline": round(events_per_sec / TARGET_EVENTS_PER_SEC, 3),
        # Device-side rate from the chained-steps probe: what the chip
        # sustains once per-step dispatch stops dominating.
        "device_events_per_sec": (
            round(width / device_step_ms * 1e3, 1) if device_step_ms > 0
            else None),
        "device_step_ms": round(device_step_ms, 4),
        "host_step_p50_ms": round(p50, 3),
        # with n=lat_iters samples the upper percentile interpolates
        # between the two worst — publish n so it reads as what it is
        "host_step_p99_ms": round(p99, 3),
        "host_step_samples": lat_iters,
        "host_rtt_ms": round(rtt * 1e3, 3),
        "latency_target_met": bool(device_step_ms < 10.0),
        "batch_width": width,
        "step_interface": "packed" if use_packed else "per-column",
        "backend": jax.default_backend(),
    })


# ---------------------------------------------------------------------------
# config 2: dispatcher path (host edge included)
# ---------------------------------------------------------------------------

def bench_dispatcher() -> None:
    """The TRUE wire path: raw NDJSON bytes -> columnar decode -> batcher
    -> jitted step -> store/outbound egress, through the real
    PipelineDispatcher — bytes-in to egress-out, with p50/p99 event
    latency from the dispatcher's per-plan samples (BASELINE.md's
    <10ms p99 applies to THIS path)."""
    reduced = require_backend() == "cpu"
    n_devices = 2_000 if reduced else 10_000
    width = 4_096 if reduced else 16_384
    lines_per_payload = 512 if reduced else 1024
    # 512 full-profile payloads ≈ 523k events: at ≥1M ev/s the timed
    # region still spans ~0.5 s — long enough to amortize the in-flight
    # window fill/drain and give a stable p99 sample set.  The reduced
    # profile uses 128×512 ≈ 65k events: a 16-payload run measured only
    # ~30 ms and swung 2× run-to-run, and 64 payloads (~0.15 s) still
    # spread 240-450k across runs — ~0.3-0.5 s halves that variance.
    n_payloads = 128 if reduced else 512
    inst = _wire_bench_instance(n_devices, width, 5.0)
    try:
        rng = np.random.default_rng(0)

        # Pre-build raw NDJSON wire payloads — the bytes a fleet would
        # actually send (JsonDecoder envelope per line, MqttTests.java
        # conformance shape).  Building them is the DEVICE's cost, so it
        # stays outside the timed region; everything after the bytes —
        # parse, resolve, batch, step, egress — is measured.
        def make_payload(r):
            lines = []
            for i in rng.integers(0, n_devices, lines_per_payload):
                lines.append(json.dumps({
                    "deviceToken": f"d-{i}",
                    "type": "Measurement",
                    "request": {"name": "temp",
                                "value": float(rng.uniform(0, 100)),
                                "eventDate": 1_753_800_000 + r},
                }, separators=(",", ":")))
            return "\n".join(lines).encode()

        payloads = [make_payload(r) for r in range(n_payloads)]

        # Warm-up compile through the dispatcher.
        inst.dispatcher.ingest_wire_lines(payloads[0])
        inst.dispatcher.flush()
        inst.dispatcher.latencies_s.clear()
        snap0 = inst.dispatcher.metrics_snapshot()
        _STAGES = ("decode", "batch", "dispatch", "ring_dispatch", "egress")
        stage0 = {}
        for stage in _STAGES:
            t = inst.metrics.timer(f"pipeline.stage_{stage}_s")
            stage0[stage] = (t.total, t.count)

        import jax as _jax

        # Dispatch-RTT probe: lower-bounds any per-plan latency, so the
        # breakdown fields below let the p99 be read against it.
        rtt_ms = measure_rtt() * 1e3

        # Single self-pacing feeder: an open-loop multi-thread burst was
        # tried and measured WORSE (GIL-bound intake contention + every
        # row pre-queued turns queueing delay into the latency number).
        t0 = time.perf_counter()
        for r in range(1, n_payloads):
            inst.dispatcher.ingest_wire_lines(payloads[r])
        inst.dispatcher.flush()
        t1 = time.perf_counter()
        n = lines_per_payload * (n_payloads - 1)
        events_per_sec = n / (t1 - t0)
        snap = inst.dispatcher.metrics_snapshot()
        p99 = snap.get("latency_p99_ms")

        # Device-resident dispatch loop accounting (ISSUE 8): how often
        # the host touched the device in the timed region — the ring's
        # whole point is driving this to 1/K — plus the per-stage host
        # attribution so every remaining millisecond of config-2 latency
        # reads against a named stage, not a black box.
        d_steps = max(1, snap["steps"] - snap0["steps"])
        host_syncs_per_batch = round(
            (snap["host_syncs"] - snap0["host_syncs"]) / d_steps, 4)
        stage_ms = {}
        for stage in _STAGES:
            t = inst.metrics.timer(f"pipeline.stage_{stage}_s")
            total0, count0 = stage0[stage]
            if t.count > count0:  # timed-region delta: the warm-up
                # compile must not masquerade as steady-state stage cost
                stage_ms[stage] = round(
                    (t.total - total0) / (t.count - count0) * 1e3, 3)

        # Latency-tuned profile: the throughput profile's p99 is
        # dominated by its 5 ms batching deadline, so a deployment that
        # cares about BASELINE.md's <10 ms p99 would run a tighter
        # deadline and smaller plans.  Reported as separate
        # latency_tuned_* fields — the throughput row stands unchanged.
        tuned = _dispatcher_tuned_latency(payloads, events_per_sec,
                                          n_devices=n_devices)

        # Device-side stage attribution: the fori-chain probes at the
        # bench width, so the doc carries BOTH halves of the latency
        # story — host stage_ms above, device stage ms here.
        from sitewhere_tpu.pipeline.telemetry import profile_device_stages

        prof = profile_device_stages(
            width=width, capacity=16_384,
            iters=(4 if reduced else 16), repeats=(2 if reduced else 3))
        device_stage_ms = {
            stage: prof[f"{stage}_ms"]
            for stage in ("validate", "rules", "zones", "state", "full")
            if f"{stage}_ms" in prof
        }
        emit({
            "metric": "dispatcher_events_per_sec_per_chip",
            "value": round(events_per_sec, 1),
            "unit": "events/s",
            "vs_baseline": round(events_per_sec / TARGET_EVENTS_PER_SEC, 3),
            "wire_path": "ndjson-bytes -> columnar decode -> step -> egress",
            "latency_p50_ms": snap.get("latency_p50_ms"),
            "latency_p99_ms": p99,
            "latency_target_met": (bool(p99 < 10.0)
                                   if p99 is not None else None),
            "host_rtt_ms": round(rtt_ms, 3),
            "deadline_ms": 5.0,
            "inflight_depth": inst.dispatcher.inflight_depth,
            # host-sync amortization: ≤1/K with the ring engaged, ~1.0
            # on the single-step path
            "host_syncs_per_batch": host_syncs_per_batch,
            "ring_depth": inst.dispatcher.ring_depth,
            # timed-region delta, like host_syncs: warm-up chains must
            # not inflate the measured run's chained coverage
            "ring_chains": int(snap["ring_chains"] - snap0["ring_chains"]),
            "stage_ms": stage_ms,
            # device-side per-stage ms (fori-chain probes) next to the
            # host attribution — both sides of the config-2 latency story
            "device_stage_ms": device_stage_ms,
            "accepted": int(snap["accepted"]),
            "steps": int(snap["steps"]),
            "backend": _jax.default_backend(),
            **({"latency_tuned_p99_ms": tuned["p99_ms"],
                "latency_tuned_target_met": bool(tuned["p99_ms"] < 10.0),
                "latency_tuned_deadline_ms": tuned["deadline_ms"],
                "latency_tuned_events_per_sec": tuned["events_per_sec"],
                "latency_tuned_attempts": tuned.get("attempts")}
               if tuned else {}),
        })
    finally:
        inst.stop()
        inst.terminate()


def _wire_bench_instance(n_devices: int, width: int, deadline_ms: float):
    """One started Instance with ``n_devices`` registered+assigned
    sensors — the shared bring-up for the dispatcher-path profiles (the
    throughput and tuned-latency regions MUST register the same fleet:
    a token the payload carries but the instance never minted resolves
    NULL_ID and silently shrinks the measured load)."""
    import tempfile

    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    tmp = tempfile.mkdtemp(prefix="swbench-")
    cfg = Config({
        "instance": {"id": "bench", "data_dir": os.path.join(tmp, "data")},
        "pipeline": {"width": width, "registry_capacity": 16384,
                     "mtype_slots": 4, "deadline_ms": deadline_ms,
                     "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "journal": {"fsync_every": 4096, "segment_bytes": 256 << 20},
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    inst.device_management.create_device_type(token="sensor", name="Sensor")
    dm = inst.device_management
    for i in range(n_devices):
        dm.create_device(token=f"d-{i}", device_type="sensor")
        dm.create_device_assignment(device=f"d-{i}")
    return inst


def _dispatcher_tuned_latency(payloads, capacity_eps, n_devices=2_000,
                              deadline_ms=3.5, width=4096, util=0.5):
    """One short wire-path region tuned for latency instead of
    throughput: tighter batching deadline, smaller plans, and — the part
    that makes the p99 a property of the PIPELINE rather than of a
    saturated queue — a PACED feeder offering ``util`` of the measured
    throughput capacity.  (The throughput region drives at saturation,
    so its p99 is queueing delay by Little's law; no deployment runs a
    latency-sensitive path at 100% utilization.)  Returns
    {p99_ms, p50_ms, events_per_sec, deadline_ms, offered_util}, or
    None when no attempt recorded a latency sample."""
    inst = _wire_bench_instance(n_devices, width, deadline_ms)
    try:
        inst.dispatcher.ingest_wire_lines(payloads[0])  # warm-up compile
        inst.dispatcher.flush()
        # (128-row payloads were tried for smoother arrivals and measured
        # WORSE: 4x the per-payload fixed intake cost cuts capacity, and
        # 4x the plans/s saturates the per-plan step budget — the p99
        # went up, not down.  The throughput profile's payload size —
        # 512 rows reduced, 1024 full — stands.)
        paced = payloads[1:]
        rows_per_payload = payloads[0].count(b"\n") + 1
        # Phase A — measure THIS instance's capacity (width/deadline
        # differ from the throughput profile's, so its capacity does
        # too; pacing against the wrong ceiling leaves the queue
        # saturated and the p99 meaningless).
        burst = paced[:max(32, len(paced) // 4)]
        tb = time.perf_counter()
        for p in burst:
            inst.dispatcher.ingest_wire_lines(p)
        inst.dispatcher.flush()
        cap = rows_per_payload * len(burst) / (time.perf_counter() - tb)
        cap = min(cap, capacity_eps) if capacity_eps else cap
        # Phase B — paced at util of measured capacity; fresh samples.
        # Two attempts, WORST p99 kept: a tail-latency claim judged on
        # the best of N is optimistically biased (the p99 of a ~1 s
        # region sits right at this host's scheduler-noise floor —
        # measured 9.6/9.8/11.3 ms across identical runs), so the
        # reported number is the one every attempt met, and all
        # attempts' p99s ride along for transparency.
        gap_s = rows_per_payload / max(cap * util, 1.0)
        worst = None
        attempt_p99s = []
        for attempt in range(2):
            inst.dispatcher.latencies_s.clear()
            t0 = time.perf_counter()
            for i, p in enumerate(paced):
                # drift-corrected pacing: each payload has an absolute
                # due time, so a slow payload doesn't permanently lower
                # the offered rate
                due = t0 + i * gap_s
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                inst.dispatcher.ingest_wire_lines(p)
            inst.dispatcher.flush()
            dt = time.perf_counter() - t0
            snap = inst.dispatcher.metrics_snapshot()
            if snap.get("latency_p99_ms") is None:
                continue
            n = rows_per_payload * len(paced)
            doc = {"p99_ms": snap["latency_p99_ms"],
                   "p50_ms": snap.get("latency_p50_ms"),
                   "events_per_sec": round(n / dt, 1),
                   "deadline_ms": deadline_ms,
                   "offered_util": util}
            attempt_p99s.append(doc["p99_ms"])
            if worst is None or doc["p99_ms"] > worst["p99_ms"]:
                worst = doc
        if worst is not None:
            worst["attempts"] = len(attempt_p99s)
            worst["attempt_p99_ms"] = attempt_p99s  # every measurement
        return worst
    finally:
        inst.stop()
        inst.terminate()


# ---------------------------------------------------------------------------
# config 3: analytics job
# ---------------------------------------------------------------------------

def bench_analytics() -> None:
    """Windowed anomaly detection over event history (sitewhere-spark
    analog; BASELINE.md config 3)."""
    import jax

    from sitewhere_tpu.analytics import build_window_grid, detect_anomalies

    reduced = require_backend() == "cpu"
    D, W, N = 16384, 168, (500_000 if reduced else 4_000_000)  # hourly windows
    rng = np.random.default_rng(0)
    device_id = rng.integers(0, D, N).astype(np.int32)
    window_idx = rng.integers(0, W, N).astype(np.int32)
    value = rng.normal(20.0, 1.0, N).astype(np.float32)
    import jax.numpy as jnp

    args = (jnp.asarray(device_id), jnp.asarray(window_idx),
            jnp.asarray(value), jnp.ones(N, bool))
    grid = build_window_grid(*args, n_devices=D, n_windows=W)
    int(detect_anomalies(grid)[0].sum())  # compile + fetch

    iters = 3 if reduced else 10
    t0 = time.perf_counter()
    for _ in range(iters):
        grid = build_window_grid(*args, n_devices=D, n_windows=W)
        anomalous, _ = detect_anomalies(grid)
    int(anomalous.sum())  # fetch: the timed region ends on the host
    t1 = time.perf_counter()
    events_per_sec = N * iters / (t1 - t0)
    emit({
        "metric": "analytics_events_per_sec_per_chip",
        "value": round(events_per_sec, 1),
        "unit": "events/s",
        "vs_baseline": round(events_per_sec / TARGET_EVENTS_PER_SEC, 3),
        "backend": __import__("jax").default_backend(),
    })


# ---------------------------------------------------------------------------
# config 4: multi-tenant fan-out + presence
# ---------------------------------------------------------------------------

def bench_multitenant() -> None:
    """8-tenant demux + presence sweep (BASELINE.md config 4): the tenant
    column partitions every table; a presence sweep over all device state
    interleaves with pipeline steps the way the reference's background
    PresenceChecker thread does (``DevicePresenceManager.java:49-88``)."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.pipeline.packed import (
        BATCH_I,
        F_ACCEPTED,
        pack_batch_host,
        pack_state,
        pack_tables,
        packed_pipeline_step,
        packed_presence_sweep,
    )

    from sitewhere_tpu.pipeline import pipeline_step
    from sitewhere_tpu.pipeline.packed import packed_step_default
    from sitewhere_tpu.schema import EventBatch
    from sitewhere_tpu.state.presence import presence_sweep

    reduced = require_backend() == "cpu"
    capacity, n_active, n_tenants = 16384, 10000, 8
    width = 16_384 if reduced else 131_072
    registry, state, rules, zones = build_tables(
        capacity, n_active, n_tenants=n_tenants)
    raw = host_batches(width, n_active, n_batches=8, n_tenants=n_tenants)

    now = jnp.int32(1_753_800_000 + 10_000)
    missing_after = jnp.int32(3600)
    use_packed = packed_step_default()  # pure-step choice (see config 1)
    if use_packed:
        tables = jax.jit(pack_tables)(registry, rules, zones)
        carry = jax.jit(pack_state)(state)
        step = jax.jit(packed_pipeline_step, donate_argnums=(1,))
        psweep = jax.jit(packed_presence_sweep, donate_argnums=(0,))
        staged = [
            tuple(jax.device_put(a) for a in pack_batch_host(b, width))
            for b in raw
        ]

        def run(c, i):
            c, oi, metrics, present = step(tables, c, *staged[i % len(staged)])
            return c, (oi, metrics)

        def do_sweep(c):
            c, newly = psweep(c, now, missing_after)
            return c, newly

        def force(out):
            return int(out[1][0])

        def accepted_mask(out):
            return (np.asarray(out[0][0]) & F_ACCEPTED) != 0
    else:
        carry = state
        step = jax.jit(pipeline_step, donate_argnums=(1,))
        staged = [
            EventBatch(**{k: jax.device_put(v) for k, v in b.items()})
            for b in raw
        ]

        def run(c, i):
            c, out = step(registry, c, rules, zones, staged[i % len(staged)])
            return c, out

        def do_sweep(c):
            return presence_sweep(c, now, missing_after)

        def force(out):
            return int(out.metrics.processed)

        def accepted_mask(out):
            return np.asarray(out.accepted)

    jax.block_until_ready(staged)
    carry, out = run(carry, 0)
    carry, newly = do_sweep(carry)
    int(newly.sum())  # compile both programs + fetch

    iters = 10 if reduced else 100
    sweep_every = 10
    t0 = time.perf_counter()
    for i in range(iters):
        carry, out = run(carry, i)
        if (i + 1) % sweep_every == 0:
            carry, newly = do_sweep(carry)
    # Fetch forces the whole donated-state chain (incl. interleaved sweeps).
    processed = force(out)
    t1 = time.perf_counter()
    assert processed == width
    # per-tenant fan-out accounting on the last step's accepted rows
    by_tenant = np.bincount(
        raw[(iters - 1) % len(raw)]["tenant_id"][accepted_mask(out)],
        minlength=n_tenants)
    events_per_sec = width * iters / (t1 - t0)
    emit({
        "metric": "multitenant_events_per_sec_per_chip",
        "value": round(events_per_sec, 1),
        "unit": "events/s",
        "vs_baseline": round(events_per_sec / TARGET_EVENTS_PER_SEC, 3),
        "tenants": n_tenants,
        "sweep_every": sweep_every,
        "min_tenant_share": round(float(by_tenant.min() / max(1, by_tenant.sum())), 4),
        "step_interface": "packed" if use_packed else "per-column",
        "backend": __import__("jax").default_backend(),
    })


# ---------------------------------------------------------------------------
# config 5: streaming media + labels (host mixed workload)
# ---------------------------------------------------------------------------

def bench_media_labels() -> None:
    """Streaming-media chunk appends + QR label renders (BASELINE.md config
    5): the non-event compute paths, both host-side by design."""
    import tempfile

    from sitewhere_tpu.labels.png import write_png
    from sitewhere_tpu.labels.qr import encode as qr_encode
    from sitewhere_tpu.services.streams import DeviceStreamManagement

    tmp = tempfile.mkdtemp(prefix="swbench5-")
    streams = DeviceStreamManagement(tmp)
    streams.start()
    try:
        chunk = os.urandom(4096)
        n_streams, chunks_per_stream = 16, 256
        t0 = time.perf_counter()
        for s in range(n_streams):
            st = streams.create_device_stream(
                assignment_token=f"a-{s}", stream_id=f"s-{s}",
                content_type="application/octet-stream")
            for i in range(chunks_per_stream):
                streams.add_device_stream_data(st.token, i, chunk)
        t1 = time.perf_counter()
        chunks_per_sec = n_streams * chunks_per_stream / (t1 - t0)
        stream_mb_per_sec = chunks_per_sec * len(chunk) / 1e6

        n_labels = 200
        scale = 4
        t2 = time.perf_counter()
        for i in range(n_labels):
            matrix = qr_encode(f"https://sitewhere-tpu.local/devices/dev-{i}")
            img = np.where(np.kron(matrix, np.ones((scale, scale), np.uint8)),
                           0, 255).astype(np.uint8)
            write_png(img)
        t3 = time.perf_counter()
        labels_per_sec = n_labels / (t3 - t2)

        # Composite ops/sec (chunk append + label render weighted equally);
        # no reference-published number exists for either path, so
        # vs_baseline is null and the sub-metrics carry the evidence.
        value = round(chunks_per_sec + labels_per_sec, 1)
        emit({
            "metric": "media_label_ops_per_sec",
            "value": value,
            "unit": "ops/s",
            "vs_baseline": None,
            "stream_chunks_per_sec": round(chunks_per_sec, 1),
            "stream_mb_per_sec": round(stream_mb_per_sec, 1),
            "qr_labels_per_sec": round(labels_per_sec, 1),
        })
    finally:
        streams.stop()


# ---------------------------------------------------------------------------
# config 6: mesh-fused ring dispatch weak-scaling sweep
# ---------------------------------------------------------------------------

def bench_mesh() -> None:
    """Mesh-fused ring dispatch (config 6): the K-deep donated-carry
    chain under ``shard_map`` swept across meshes of 1/2/4/8 of the
    visible devices — the chips of a TPU host, or (under
    ``JAX_PLATFORMS=cpu``) 8 forced host devices.

    WEAK scaling by construction: every scale carries a fixed 32 rows
    per device per round, so the aggregate ev/s ladder measures what the
    mesh buys — per-round host overhead (intake, plan bookkeeping, ONE
    shared D2H fetch per K-chain) amortized over n× the rows.  Intake is
    the zero-copy lane end to end: pre-built columns committed through
    fill-direct reservations the sharded batcher ADOPTS, so the ladder
    isn't a memcpy bench.

    On the CPU backend the per-device executions of the shard_map
    program interleave on the host's cores and every multi-device
    execution pays a rendezvous, so the CPU ladder shows counts, not
    scaling.  The host-side contract — ``host_syncs == steps/K`` at
    every scale — is asserted per scale on any backend.  Each scale
    reports the MEDIAN of several trials."""
    import tempfile

    if asked_for_cpu():
        # 8 virtual host devices BEFORE any backend initializes
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    reduced = require_backend() == "cpu"

    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    K = 8
    per_dev = 32        # rows per device per round, fixed across scales
    chains = 3 if reduced else 4      # timed K-chains per trial
    trials = 3 if reduced else 7
    tmp = tempfile.mkdtemp(prefix="swbench6-")
    ts0 = 1_754_500_000
    scales: dict[int, dict] = {}
    flight_dump = None

    for n in (n for n in (1, 2, 4, 8) if n <= len(jax.devices())):
        width = per_dev * n
        cap = width
        seg = width // n            # rows per shard per round
        rps = cap // n              # registry rows per shard block
        pipeline = {"width": width, "registry_capacity": cap,
                    "mtype_slots": 4, "deadline_ms": 200.0,
                    "ring_depth": K}
        if n > 1:
            pipeline["n_shards"] = n
        cfg = Config({
            "instance": {"id": f"bench-mesh-{n}",
                         "data_dir": os.path.join(tmp, f"mesh-{n}")},
            "pipeline": pipeline,
            "presence": {"scan_interval_s": 3600.0,
                         "missing_after_s": 1800},
        }, apply_env=False)
        inst = Instance(cfg)
        inst.start()
        try:
            dm = inst.device_management
            dm.create_device_type(token="sensor", name="Sensor")
            for i in range(cap):
                dm.create_device(token=f"d-{i}", device_type="sensor")
                dm.create_device_assignment(device=f"d-{i}")
            handles = np.asarray(inst.identity.device.lookup_many(
                [f"d-{i}" for i in range(cap)]), np.int32)
            by_shard = [handles[(handles // rps) == s] for s in range(n)]
            rng = np.random.default_rng(6)
            d = inst.dispatcher

            # Pre-built balanced traffic (building rows is the fleet's
            # cost, outside the timed region): shard-block-ordered full
            # rounds, so every emission is ring-eligible on every shard
            # and every reservation is ADOPTED (zero-copy).
            n_rounds = K + trials * chains * K
            devs = [np.concatenate([
                rng.choice(by_shard[s], seg) for s in range(n)
            ]).astype(np.int32) for _ in range(n_rounds)]
            vals = [rng.uniform(0, 100, width).astype(np.float32)
                    for _ in range(n_rounds)]

            def ingest(r):
                res = d.batcher.reserve(width)
                res.device_id[:width] = devs[r]
                res.mtype_id[:width] = 0
                res.value[:width] = vals[r]
                res.ts_s[:width] = ts0 + r
                res.ts_ns[:width] = 0
                res.update_state[:width] = 1
                res.n = width
                d.ingest_wire_decoded(b"", res, [], source_id="bench")

            r = 0
            for _ in range(K):          # warm: one full chain (compile)
                ingest(r)
                r += 1
            d.flush()
            snap0 = d.metrics_snapshot()
            t_ring = inst.metrics.timer("pipeline.stage_ring_dispatch_s")
            t_wait = inst.metrics.timer("pipeline.stage_ring_wait_s")
            ring0 = (t_ring.total, t_ring.count)
            wait0 = t_wait.total

            evs = []
            for _ in range(trials):
                rounds = chains * K
                t0 = time.perf_counter()
                for _ in range(rounds):
                    ingest(r)
                    r += 1
                d.flush()
                t1 = time.perf_counter()
                evs.append(rounds * width / (t1 - t0))
            evs.sort()

            snap = d.metrics_snapshot()
            d_steps = snap["steps"] - snap0["steps"]
            d_syncs = snap["host_syncs"] - snap0["host_syncs"]
            ring_n = t_ring.count - ring0[1]
            copied = inst.metrics.snapshot()["counters"].get(
                "pipeline.bytes_copied.batch", 0)
            scales[n] = {
                "ev_per_s": round(evs[len(evs) // 2], 1),
                "ev_per_s_trials": [round(e, 1) for e in evs],
                "steps": int(d_steps),
                "host_syncs": int(d_syncs),
                "host_syncs_per_batch": round(d_syncs / max(1, d_steps), 4),
                "host_syncs_ok": bool(d_syncs * K == d_steps),
                "stage_ms_ring_dispatch": (
                    round((t_ring.total - ring0[0]) / ring_n * 1e3, 3)
                    if ring_n else None),
                "chain_ms": (
                    round((t_ring.total - ring0[0]
                           + t_wait.total - wait0) / ring_n * 1e3, 3)
                    if ring_n else None),
                "bytes_copied_batch": int(copied),
            }
            emit(dict(scales[n], n_devices=n, provisional=True))
            if inst.flightrec is not None:   # the largest scale's survives
                flight_dump = inst.flightrec.snapshot("bench-mesh")
        finally:
            inst.stop()
            inst.terminate()

    ev1 = scales[1]["ev_per_s"]
    for s in scales.values():
        s["speedup_vs_1"] = round(s["ev_per_s"] / ev1, 2)
    # The mesh premium: what one K-chain execution costs on the
    # smallest mesh over the single-chip chain at the SAME per-device
    # width.
    premium = None
    if 2 in scales and scales[1]["chain_ms"] and scales[2]["chain_ms"]:
        premium = round(scales[2]["chain_ms"] - scales[1]["chain_ms"], 3)
    top = max(scales)
    head = scales[top]
    emit({
        "metric": "mesh_events_per_sec_aggregate",
        "value": head["ev_per_s"],
        "unit": "events/s",
        "vs_baseline": None,
        "backend": jax.default_backend(),
        "ring_depth": K,
        "events_per_device_per_round": per_dev,
        "weak_scaling": True,
        "n_devices": top,
        "speedup_vs_1": head["speedup_vs_1"],
        "host_syncs_per_batch": head["host_syncs_per_batch"],
        "stage_ms_ring_dispatch": head["stage_ms_ring_dispatch"],
        "mesh_chain_premium_ms": premium,
        "scales": scales,
        "flightrec_dump": flight_dump,
    })


CONFIGS = {
    1: bench_pipeline,
    2: bench_dispatcher,
    3: bench_analytics,
    4: bench_multitenant,
    5: bench_media_labels,
    6: bench_mesh,
}


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=int, default=1,
                        choices=sorted(CONFIGS),
                        help="benchmark config (BASELINE.md; 6 = mesh "
                             "weak-scaling sweep); default 1")
    args = parser.parse_args()

    from sitewhere_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    CONFIGS[args.config]()


if __name__ == "__main__":
    main()

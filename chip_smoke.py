#!/usr/bin/env python3
"""chip_smoke.py — the served event path, once, on the TPU, at shipped size.

The quickest proof that the system still starts on the chip.  One
process: it fails at once unless ``jax.devices()[0].platform`` is
``tpu``, then

1. builds the native wire decoder from ``swwire.c`` (never trusts a
   ``.so`` left in the tree);
2. compiles and runs the Pallas geofence kernel (``interpret=False``)
   against the dense path;
3. starts an ``Instance`` at the shipped pipeline defaults
   (``pipeline.width`` 65,536, ``pipeline.registry_capacity`` 1<<20,
   ``mtype_slots`` 8), registers a fleet with assignments, one
   threshold rule and one geofence zone, calibrates the hung-step
   watchdog from the instance's own device-stage profile, and feeds
   two legs —
   * wire:   NDJSON payloads of 1,024 lines into
     ``dispatcher.ingest_wire_lines``, a window at a time, each window
     offered while the overload controller is NORMAL (deadline-emitted
     partial plans, single-step path);
   * column: full-width batches into ``dispatcher.ingest_arrays``
     (ring-eligible fill plans: the K=8 donated chain with one shared
     D2H fetch) —
   then flushes, queries the store and reads sampled devices' state
   back, comparing every count and value with plain numpy computed from
   the generated inputs (``--seed``);
4. repeats 3 with ``pipeline.n_shards: 4`` when four chips are present
   (state must sit on all four; how many plans chained is reported,
   not required — one feeder cannot fill the mesh ring in its window).

No rung below the chip's default path passes: breaker off ``chained``,
a watchdog trip, a quarantine, a dead letter, a host-copy error, a
Python-path decode, an unstaged batch or a failed warm-up all fail the
run.  Every check is printed; the last stdout line is
``{"ok": true, "device": {...}}`` only when all of them held.

``run_leg`` takes its sizes as arguments so tier-1 can drive the same
body at toy size on CPU (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

THRESHOLD = 90.0
# geofence rectangle, asymmetric so a lat/lon swap cannot pass
ZONE_LAT = (-4.0, 6.0)
ZONE_LON = (-8.0, 3.0)

MEASUREMENT, LOCATION = 0, 1      # schema.EventType, checked in run_leg


def fires_threshold(etype, value):
    """The numpy reference of the threshold rule: measurements only."""
    return (etype == MEASUREMENT) & (value > np.float32(THRESHOLD))


def fires_zone(etype, lat, lon):
    """The numpy reference of the geofence: locations in the rectangle."""
    return ((etype == LOCATION)
            & (lat > ZONE_LAT[0]) & (lat < ZONE_LAT[1])
            & (lon > ZONE_LON[0]) & (lon < ZONE_LON[1]))


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Checks:
    """Every comparison the smoke makes, printed as it is made."""

    def __init__(self) -> None:
        self.failed: list = []

    def check(self, name: str, ok, detail: str = "") -> bool:
        ok = bool(ok)
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def equal(self, name: str, got, want) -> bool:
        return self.check(name, got == want, f"got {got}, want {want}")


class CompileMeter:
    """Seconds XLA spent compiling (or loading from the persistent
    cache) and the cache's hit/miss counts, from JAX's own monitoring
    events; ``take()`` returns the totals since the previous take."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self._s = 0.0
        self._n = 0
        self._hits = 0
        self._misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            self._s += seconds
            self._n += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == _HIT_EVENT:
            self._hits += 1
        elif event == _MISS_EVENT:
            self._misses += 1

    def take(self) -> dict:
        out = {"compile_s": round(self._s, 2), "programs": self._n,
               "cache_hits": self._hits, "cache_misses": self._misses}
        self._s, self._n, self._hits, self._misses = 0.0, 0, 0, 0
        return out


def device_doc() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_doc() -> list:
    """Per-device ``memory_stats()`` ([] on backends without them)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats:
            out.append({"id": d.id,
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                        "bytes_limit": stats.get("bytes_limit")})
    return out


# ---------------------------------------------------------------------------
# Pallas geofence kernel vs the dense path
# ---------------------------------------------------------------------------

def pallas_check(checks: Checks, b: int, z: int, v: int, seed: int,
                 interpret: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.ops.geo import pad_polygon, points_in_polygons
    from sitewhere_tpu.ops.geo_pallas import points_in_polygons_pallas

    rng = np.random.default_rng(seed)
    polys = []
    for _ in range(z):
        n = int(rng.integers(3, v + 1))
        angles = np.sort(rng.uniform(0, 2 * np.pi, n))
        cx, cy = rng.uniform(-50, 50, 2)
        r = rng.uniform(1, 20)
        polys.append(pad_polygon(np.stack(
            [cx + r * np.cos(angles), cy + r * np.sin(angles)], axis=1), v))
    verts = jnp.asarray(np.stack(polys))
    points = jnp.asarray(rng.uniform(-60, 60, (b, 2)).astype(np.float32))

    t0 = time.perf_counter()
    tiled = points_in_polygons_pallas(points, verts, interpret=interpret)
    tiled.block_until_ready()
    first_s = time.perf_counter() - t0
    dense = jax.jit(points_in_polygons)(points, verts)
    mismatches = int(jnp.sum(dense != tiled))
    inside = int(jnp.sum(dense))
    print(f"[pallas] ({b},{z},{v}) interpret={interpret}: first call "
          f"{first_s:.2f}s, {inside} containments, {mismatches} mismatches "
          f"of {b * z}", flush=True)
    checks.check("pallas kernel shape", tiled.shape == (b, z),
                 str(tiled.shape))
    checks.check("pallas kernel matches the dense path", mismatches == 0,
                 f"{mismatches} of {b * z} pairs differ")
    checks.check("pallas comparison is not vacuous", inside > 0)
    return {"shape": [b, z, v], "interpret": interpret,
            "first_call_s": round(first_s, 2), "mismatches": mismatches}


# ---------------------------------------------------------------------------
# one leg pair through a running Instance
# ---------------------------------------------------------------------------

def _drain(inst, timeout_s: float = 300.0) -> None:
    """flush() until the dispatcher is quiescent: nothing pending and the
    step count stopped moving (derived alerts re-enter the batcher, so
    one flush is not always the last)."""
    d = inst.dispatcher
    deadline = time.monotonic() + timeout_s
    last = -1
    while time.monotonic() < deadline:
        d.flush(timeout_s=60.0)
        snap = d.metrics_snapshot()
        if snap["pending_rows"] == 0 and snap["steps"] == last:
            return
        last = snap["steps"]
    raise RuntimeError(f"dispatcher did not drain in {timeout_s:.0f}s")


def _await_normal(inst, timeout_s: float = 60.0) -> float:
    """Wait until the overload controller is back to NORMAL; returns the
    seconds waited.  Admission sheds telemetry from DEGRADED up, so the
    wire leg offers each window to a controller that would admit it."""
    from sitewhere_tpu.runtime.overload import OverloadState

    t0 = time.monotonic()
    while inst.overload.state != OverloadState.NORMAL:
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(
                f"overload controller stuck in {inst.overload.state.name}")
        time.sleep(0.05)
    return time.monotonic() - t0


def _register_fleet(inst, n_devices: int, n_shards: int, capacity: int):
    """Devices with assignments through the management API.  Handles
    are minted densely, and a registry block belongs to shard
    ``handle // rows_per_shard`` — so on a mesh the fleet is laid out a
    quarter per shard by reserving the handles in between (a fleet
    registered back to back would sit on shard 0 alone; see PERF.md)."""
    dm = inst.device_management
    dm.create_device_type(token="sensor", name="Sensor")
    dm.create_area_type(token="bldg", name="Building")
    dm.create_area(token="hq", name="HQ", area_type="bldg")
    per_shard = n_devices // n_shards
    rows_per_shard = capacity // n_shards
    tokens = []
    for s in range(n_shards):
        for i in range(len(inst.identity.device), s * rows_per_shard):
            inst.identity.device.mint(f"reserved-{i}")
        for i in range(per_shard):
            token = f"d-{s}-{i}"
            dm.create_device(token=token, device_type="sensor")
            dm.create_device_assignment(device=token, area="hq")
            tokens.append(token)
    handles = np.asarray(inst.identity.device.lookup_many(tokens), np.int32)
    return tokens, handles


def run_leg(checks: Checks, meter: CompileMeter, *, capacity: int,
            width: int, n_devices: int, n_shards: int = 1,
            ring_depth=None, wire_windows: int = 8, wire_payloads: int = 8,
            wire_lines: int = 1024, column_batches: int = 64,
            sample: int = 128, seed: int = 0) -> dict:
    """Wire leg + column leg through one ``Instance``; returns the
    report.  Sizes are arguments; everything else is the shipped
    configuration.  ``ring_depth`` None = the backend's default."""
    from sitewhere_tpu import native
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.pipeline import packed
    from sitewhere_tpu.runtime.config import Config
    from sitewhere_tpu.runtime.overload import OverloadShed
    from sitewhere_tpu.schema import AlertLevel, ComparisonOp, EventType

    if (MEASUREMENT, LOCATION) != (int(EventType.MEASUREMENT),
                                   int(EventType.LOCATION)):
        raise RuntimeError("chip_smoke's event-type constants are stale")
    tag = f"leg n_shards={n_shards}"
    print(f"[{tag}] capacity={capacity} width={width} devices={n_devices} "
          f"wire={wire_windows}x{wire_payloads}x{wire_lines} "
          f"column={column_batches}x{width} seed={seed}", flush=True)
    rng = np.random.default_rng(seed)
    # recent stamps: the presence sweep must not find the fleet missing
    base_ts = int(time.time()) - 3600
    copy_errors0 = packed.host_copy_errors
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    pipeline = {"width": width, "registry_capacity": capacity,
                "n_shards": n_shards}
    if ring_depth is not None:
        pipeline["ring_depth"] = ring_depth
    t0 = time.perf_counter()
    inst = Instance(Config({
        "instance": {"id": "chip-smoke",
                     "data_dir": os.path.join(tmp, "data")},
        "pipeline": pipeline,
    }, apply_env=False))
    inst.start()
    report: dict = {"n_shards": n_shards, "capacity": capacity,
                    "width": width, "devices": n_devices}
    try:
        d = inst.dispatcher
        report["start"] = dict(meter.take(),
                               wall_s=round(time.perf_counter() - t0, 2))
        print(f"[{tag}] instance started: {report['start']}", flush=True)
        checks.check(f"{tag}: warm-up dispatch succeeded",
                     d.warm_error is None, repr(d.warm_error))

        t0 = time.perf_counter()
        tokens, handles = _register_fleet(inst, n_devices, n_shards, capacity)
        n_dev = len(tokens)
        reg_s = time.perf_counter() - t0
        report["registered"] = n_dev
        print(f"[{tag}] registered {n_dev} devices with assignments in "
              f"{reg_s:.1f}s ({n_dev / reg_s:.0f}/s)", flush=True)
        inst.rules.create_rule(mtype=None, op=ComparisonOp.GT,
                               threshold=THRESHOLD, alert_type="hot",
                               alert_level=AlertLevel.WARNING)
        inst.device_management.create_zone(
            token="z1", name="Z1", area="hq", alert_type="inside",
            bounds=[(ZONE_LAT[0], ZONE_LON[0]), (ZONE_LAT[0], ZONE_LON[1]),
                    (ZONE_LAT[1], ZONE_LON[1]), (ZONE_LAT[1], ZONE_LON[0])])
        mtype = int(inst.identity.mtype.mint("temp"))
        slot = mtype % int(inst.config["pipeline.mtype_slots"])

        # The watchdog's budgets (1 s soft, 10 s hard) are placeholders
        # until calibrated against the measured step: the instance's
        # own profile does that, and prints where the step's time goes.
        wd = d.watchdog
        meter.take()
        t0 = time.perf_counter()
        profile = inst.run_device_profile(iters=8, repeats=3)
        report["device_profile"] = dict(
            profile, **meter.take(),
            wall_s=round(time.perf_counter() - t0, 2),
            watchdog_soft_s=wd.soft_s, watchdog_hard_s=wd.hard_s)
        print(f"[{tag}] device stage profile: "
              f"{json.dumps(report['device_profile'])}", flush=True)

        # every source event of both legs, for the numpy reference
        src = {k: [] for k in ("dev", "etype", "ts_s", "ts_ns", "value",
                               "lat", "lon")}

        def remember(dev, etype, ts_s, ts_ns, value, lat, lon):
            for k, v in zip(src, (dev, etype, ts_s, ts_ns, value, lat, lon)):
                src[k].append(v)

        stage_names = ("decode", "batch", "dispatch", "ring_wait",
                       "ring_dispatch", "egress")

        def stage_marks():
            timers = [inst.metrics.timer(f"pipeline.stage_{s}_s")
                      for s in stage_names]
            return [(t.total, t.count) for t in timers]

        def leg_counts(before, after, store_before, marks):
            keys = ("processed", "accepted", "unregistered", "unassigned",
                    "threshold_alerts", "zone_alerts", "derived_alerts",
                    "steps", "ring_chains", "host_syncs")
            out = {k: int(after[k]) - int(before[k]) for k in keys}
            out["stored"] = inst.event_store.total_events - store_before
            # host-clock mean per observation of each dispatcher stage
            out["stage_mean_ms"] = {
                name: round((t1 - t0) / (c1 - c0) * 1e3, 3)
                for name, (t0, c0), (t1, c1)
                in zip(stage_names, marks, stage_marks()) if c1 > c0}
            out["overload"] = inst.overload.state.name
            return out

        def compare(name, got, n, thr, zone):
            derived = thr + zone
            checks.equal(f"{tag} {name}: processed", got["processed"],
                         n + derived)
            checks.equal(f"{tag} {name}: accepted", got["accepted"],
                         n + derived)
            checks.equal(f"{tag} {name}: threshold alerts = "
                         f"count(value > {THRESHOLD})",
                         got["threshold_alerts"], thr)
            checks.equal(f"{tag} {name}: zone alerts = points in rectangle",
                         got["zone_alerts"], zone)
            checks.equal(f"{tag} {name}: derived alerts re-injected",
                         got["derived_alerts"], derived)
            checks.equal(f"{tag} {name}: stored = accepted + derived",
                         got["stored"], n + derived)
            checks.equal(f"{tag} {name}: unregistered + unassigned",
                         got["unregistered"] + got["unassigned"], 0)

        # -- wire leg: NDJSON bytes, a window at a time ---------------------
        n_payloads = wire_windows * wire_payloads
        payloads = []
        thr_wire = 0
        for r in range(n_payloads):
            # one stamp per payload, each device at most once in it: a
            # device's newest event is never a tie
            pick = rng.permutation(n_dev)[:wire_lines]
            vals = np.round(rng.uniform(0, 100, len(pick)), 3)
            payloads.append("\n".join(
                f'{{"deviceToken":"{tokens[i]}","type":"Measurement",'
                f'"request":{{"name":"temp","value":{v!r},'
                f'"eventDate":{base_ts + r}}}}}'
                for i, v in zip(pick.tolist(), vals.tolist())).encode())
            v32 = vals.astype(np.float32)
            n = len(pick)
            thr_wire += int(fires_threshold(np.zeros(n, np.int32), v32).sum())
            remember(handles[pick], np.zeros(n, np.int32),
                     np.full(n, base_ts + r, np.int64), np.zeros(n, np.int64),
                     v32, np.zeros(n, np.float32), np.zeros(n, np.float32))
        n_wire = sum(len(x) for x in src["dev"])
        before, store0 = d.metrics_snapshot(), inst.event_store.total_events
        marks = stage_marks()
        meter.take()
        shed = 0
        left_normal = 0
        waited = 0.0
        t0 = time.perf_counter()
        for w in range(wire_windows):
            waited += _await_normal(inst)
            for p in payloads[w * wire_payloads:(w + 1) * wire_payloads]:
                try:
                    d.ingest_wire_lines(p)
                except OverloadShed:
                    shed += 1
            _drain(inst)
            left_normal += inst.overload.state.name != "NORMAL"
        wire = leg_counts(before, d.metrics_snapshot(), store0, marks)
        wire.update(meter.take(), wall_s=round(time.perf_counter() - t0, 2),
                    events=n_wire, windows_left_normal=left_normal,
                    awaited_normal_s=round(waited, 2), memory=memory_doc())
        report["wire"] = wire
        print(f"[{tag}] wire leg: {json.dumps(wire)}", flush=True)
        checks.equal(f"{tag} wire: payloads shed by admission", shed, 0)
        compare("wire", wire, n_wire, thr_wire, 0)

        # -- column leg: full-width pre-resolved batches --------------------
        by_shard = [handles[s * (n_dev // n_shards):
                            (s + 1) * (n_dev // n_shards)]
                    for s in range(n_shards)]
        seg = width // n_shards
        batches = []
        thr_col = zone_col = 0
        # A ring's worth goes in back to back, then the leg drains: a
        # plan that queues behind a full in-flight window stays in
        # flight for seconds at a 162 ms step.  The first six rings fire
        # no rule — a derived alert re-enters the batcher, leaves on a
        # partial plan, and that plan's ordering barrier steps ring-held
        # predecessors one by one (dispatcher._run_plan) — so whether
        # they chain depends only on the feeder beating the ring's
        # age-out (4 to 7 of the 8 rings did, per run, on the v5e
        # host).  The last two fire ~9%.
        ring = max(d.ring_depth, 1)
        quiet = column_batches - 2 * ring
        for b in range(column_batches):
            # shard-block order, seg rows per shard: every emission is a
            # full-width fill plan on any mesh
            dev = np.concatenate([rng.choice(by_shard[s], seg)
                                  for s in range(n_shards)]).astype(np.int32)
            etype = (rng.random(width) < 0.5).astype(np.int32)
            ts_s = np.full(width, base_ts + 1000 + b, np.int32)
            ts_ns = (rng.permutation(width) * 1000).astype(np.int32)
            value = rng.uniform(0, THRESHOLD if b < quiet else 100,
                                width).astype(np.float32)
            lat = rng.uniform(-20, 20, width).astype(np.float32)
            lon = rng.uniform(ZONE_LON[1] + 1 if b < quiet else -20, 20,
                              width).astype(np.float32)
            batches.append(dict(
                device_id=dev, event_type=etype, ts_s=ts_s, ts_ns=ts_ns,
                mtype_id=np.full(width, mtype, np.int32), value=value,
                lat=lat, lon=lon))
            thr_col += int(fires_threshold(etype, value).sum())
            zone_col += int(fires_zone(etype, lat, lon).sum())
            remember(dev, etype, ts_s.astype(np.int64),
                     ts_ns.astype(np.int64), value, lat, lon)
        before, store0 = d.metrics_snapshot(), inst.event_store.total_events
        h2d0 = inst.metrics.counter("pipeline.bytes_copied.h2d").value
        marks = stage_marks()
        t0 = time.perf_counter()
        for b, cols in enumerate(batches):
            if b and b % ring == 0:
                _drain(inst)
            d.ingest_arrays(**cols)
        _drain(inst)
        column = leg_counts(before, d.metrics_snapshot(), store0, marks)
        staged = int(inst.metrics.counter(
            "pipeline.bytes_copied.h2d").value - h2d0)
        column.update(meter.take(),
                      wall_s=round(time.perf_counter() - t0, 2),
                      events=column_batches * width, staged_bytes=staged,
                      memory=memory_doc())
        report["column"] = column
        print(f"[{tag}] column leg: {json.dumps(column)}", flush=True)
        compare("column", column, column_batches * width, thr_col, zone_col)
        chained = (f"{column['ring_chains']} chains of {d.ring_depth}, "
                   f"{column['steps']} steps, {column['host_syncs']} host "
                   f"syncs")
        if n_shards == 1:
            checks.check(f"{tag} column: ring_chains >= 2",
                         column["ring_chains"] >= 2, chained)
        else:
            # Reported, not required: the sharded batcher routes every
            # column through per-shard gathers (~18 ms a batch on the
            # 4-chip host), so one feeder cannot put 8 plans in the ring
            # inside the 40 ms window and the loop thread ages it out.
            # On a mesh the fused chain runs at warm-up only (PERF.md).
            print(f"  [--] {tag} column: {chained}", flush=True)
        print(f"[{tag}] chained plans: wire "
              f"{wire['ring_chains'] * d.ring_depth} of {wire['steps']}, "
              f"column {column['ring_chains'] * d.ring_depth} of "
              f"{column['steps']}", flush=True)

        # -- the store and the state, read back -----------------------------
        all_src = {k: np.concatenate(v) for k, v in src.items()}
        key = all_src["ts_s"] * 1_000_000_000 + all_src["ts_ns"]
        meas = all_src["etype"] == MEASUREMENT
        n_src = len(key)
        n_derived = thr_wire + thr_col + zone_col
        store = inst.event_store
        checks.equal(f"{tag}: store total", store.total_events,
                     n_src + n_derived)
        checks.equal(f"{tag}: stored ALERT events = derived",
                     store.query(event_type=int(EventType.ALERT)).total,
                     n_derived)
        probe = int(all_src["dev"][0])
        on_probe = all_src["dev"] == probe
        fired = (fires_threshold(all_src["etype"], all_src["value"])
                 | fires_zone(all_src["etype"], all_src["lat"],
                              all_src["lon"]))
        checks.equal(f"{tag}: store query for device {probe}",
                     store.query(device_id=probe).total,
                     int(on_probe.sum() + (on_probe & fired).sum()))

        picked = rng.choice(np.unique(all_src["dev"]),
                            min(sample, n_dev), replace=False)
        bad = []
        for dev in picked.tolist():
            rows = np.nonzero(all_src["dev"] == dev)[0]
            newest = rows[np.argmax(key[rows])]
            want = {"last_event_ts_s": int(all_src["ts_s"][newest]),
                    "last_event_type": int(all_src["etype"][newest])}
            mrows, lrows = rows[meas[rows]], rows[~meas[rows]]
            if len(mrows):
                m = mrows[np.argmax(key[mrows])]
                want["value"] = float(all_src["value"][m])
                want["value_ts_s"] = int(all_src["ts_s"][m])
            if len(lrows):
                at = lrows[np.argmax(key[lrows])]
                want["lat"] = float(all_src["lat"][at])
                want["lon"] = float(all_src["lon"][at])
                want["loc_ts_s"] = int(all_src["ts_s"][at])
            row = inst.device_state.get_device_state_by_id(dev)
            got = {"last_event_ts_s": row["last_event_ts_s"],
                   "last_event_type": row["last_event_type"]}
            if len(mrows):
                got["value"] = row["last_values"][slot]
                got["value_ts_s"] = row["last_value_ts_s"][slot]
            if len(lrows):
                got["lat"] = row["last_location"]["lat"]
                got["lon"] = row["last_location"]["lon"]
                got["loc_ts_s"] = row["last_location"]["ts_s"]
            if got != want:
                bad.append((dev, got, want))
        checks.check(f"{tag}: state of {len(picked)} sampled devices = "
                     f"their newest events", not bad,
                     f"{len(bad)} differ, first: {bad[:1]}")

        # -- nothing below the default rung ---------------------------------
        snap = d.metrics_snapshot()
        fault = snap["device_fault"]
        checks.check(f"{tag}: breaker at 'chained' with zero trips",
                     fault["breaker"]["levelName"] == "chained"
                     and fault["breaker"]["trips"] == 0,
                     json.dumps(fault["breaker"]))
        # a SOFT trip is a flight record, not a fault: since the step
        # costs the batch the calibrated soft budget (50 x the probe's
        # device stage, ~0.9 s) is about the age of the oldest of sixteen
        # plans queued for egress, so a burst of column batches may trip
        # it (PERF.md §6, ROADMAP D12); a hard trip marks the tier
        # unhealthy and fails here
        checks.check(f"{tag}: watchdog never tripped hard",
                     fault["watchdog"]["hardTrips"] == 0
                     and not fault["watchdog"]["unhealthy"],
                     json.dumps(fault["watchdog"]))
        checks.equal(f"{tag}: quarantined devices",
                     fault["quarantined_devices"], 0)
        letters = inst.list_dead_letters(limit=5)
        checks.check(f"{tag}: dead-letter journal empty", not letters,
                     f"{[x.get('kind') for x in letters]}")
        checks.equal(f"{tag}: egress failures", d.egress_failures, 0)
        checks.equal(f"{tag}: host_copy_errors",
                     packed.host_copy_errors - copy_errors0, 0)
        checks.equal(f"{tag}: native.build_fallbacks",
                     native.build_fallbacks, 0)
        bytes_copied = inst.metrics.counter(
            "pipeline.bytes_copied.decode").value
        checks.equal(f"{tag}: wire payloads decoded by the native "
                     f"fill-direct scanner (intermediate bytes)",
                     int(bytes_copied), 0)
        report["switches"] = {
            "ring_depth": d.ring_depth,
            "inflight_depth": d.inflight_depth,
            "ring_donate": d._ring_donate,
            "egress_offload": d.egress_offload,
            "cost_analysis": d.cost_analysis,
            "batch_staging": packed.supports_batch_staging(),
        }
        report["cost"] = {
            k: inst.metrics.gauge(f"device.cost.{k}").value
            for k in ("flops", "bytes_accessed")}
        print(f"[{tag}] switches: {json.dumps(report['switches'])} "
              f"cost: {json.dumps(report['cost'])}", flush=True)
        if n_shards > 1:
            state = inst.device_state.current
            placed = len(state.last_event_ts_s.sharding.device_set)
            checks.equal(f"{tag}: state sharded over devices", placed,
                         n_shards)
            ps = inst.device_state.current_packed
            share = ps.rows.nbytes // n_shards
            mem = memory_doc()[:n_shards]
            if mem:
                checks.check(
                    f"{tag}: every chip holds its share of the state "
                    f"(>= {share} bytes)",
                    all(m["bytes_in_use"] >= share for m in mem),
                    json.dumps([m["bytes_in_use"] for m in mem]))
    finally:
        inst.stop()
        inst.terminate()
        shutil.rmtree(tmp, ignore_errors=True)
    del inst
    gc.collect()
    return report


def check_chip_side(checks: Checks, leg: dict) -> None:
    """The side of each backend switch the chip must have taken."""
    tag = f"leg n_shards={leg['n_shards']}"
    sw = leg["switches"]
    checks.equal(f"{tag}: ring_depth", sw["ring_depth"], 8)
    checks.equal(f"{tag}: inflight_depth", sw["inflight_depth"], 16)
    for name in ("ring_donate", "egress_offload", "cost_analysis",
                 "batch_staging"):
        checks.equal(f"{tag}: {name}", sw[name], True)
    checks.check(f"{tag}: cost analysis of the compiled chain recorded",
                 leg["cost"]["flops"] > 0 and leg["cost"]["bytes_accessed"] > 0,
                 json.dumps(leg["cost"]))
    plan_bytes = 16 * leg["width"] * 4     # [12, B] int32 + [4, B] float32
    checks.check(f"{tag}: every column plan was staged ahead of its step",
                 leg["column"]["staged_bytes"]
                 >= leg["column"]["events"] // leg["width"] * plan_bytes,
                 f"{leg['column']['staged_bytes']} bytes")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    # the periodic checkpoint deep-copies and pickles the management
    # stores: 6 s at 131,072 devices, 13 s at 262,144 (sandbox CPU), of
    # every 30 s, sharing the interpreter with dispatch and egress
    p.add_argument("--devices", type=int, default=131_072,
                   help="fleet size (at least 100,000 at shipped size)")
    p.add_argument("--column-batches", type=int, default=64)
    args = p.parse_args()

    import jax

    device = device_doc()
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"count: {device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU — refusing to run (this script never "
              "runs small on another backend)", file=sys.stderr)
        return 1

    from sitewhere_tpu import native
    from sitewhere_tpu.runtime.compile_cache import enable_compile_cache
    from sitewhere_tpu.runtime.config import DEFAULTS

    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    checks, meter = Checks(), CompileMeter()
    t_all = time.perf_counter()

    t0 = time.perf_counter()
    mod = native.build_swwire()
    print(f"[native] built {os.path.basename(mod.__file__)} from swwire.c "
          f"in {time.perf_counter() - t0:.1f}s", flush=True)

    report = {"device": device, "cache_dir": cache_dir,
              "pallas": pallas_check(checks, 131072, 512, 16, args.seed)}
    report["pallas"].update(meter.take())

    sizes = dict(capacity=DEFAULTS["pipeline"]["registry_capacity"],
                 width=DEFAULTS["pipeline"]["width"],
                 n_devices=args.devices,
                 column_batches=args.column_batches, seed=args.seed)
    legs = [run_leg(checks, meter, n_shards=1, **sizes)]
    if len(jax.devices()) >= 4:
        legs.append(run_leg(checks, meter, n_shards=4, **sizes))
    for leg in legs:
        check_chip_side(checks, leg)
        checks.check(f"leg n_shards={leg['n_shards']}: fleet >= 100,000",
                     leg["registered"] >= 100_000, str(leg["registered"]))
    report["legs"] = legs

    phases = [report["pallas"]] + [
        leg[ph] for leg in legs
        for ph in ("start", "device_profile", "wire", "column")]
    compile_s = sum(ph["compile_s"] for ph in phases)
    hits = sum(ph["cache_hits"] for ph in phases)
    misses = sum(ph["cache_misses"] for ph in phases)
    # cold: more programs compiled and written than found in the cache
    # (a warm run may still write one that compiled faster than JAX's
    # 1 s persistence threshold the first time)
    report["compile"] = {"state": "cold" if misses > hits else "warm",
                         "seconds": round(compile_s, 2),
                         "cache_hits": hits, "cache_misses": misses}
    report["peak_bytes_in_use"] = [m["peak_bytes_in_use"]
                                   for m in memory_doc()]
    report["wall_s"] = round(time.perf_counter() - t_all, 1)
    print(f"{report['compile']['state']} compile seconds: "
          f"{report['compile']['seconds']} (cache hits {hits}, misses "
          f"{misses}); peak device "
          f"memory: {report['peak_bytes_in_use']}; wall {report['wall_s']}s",
          flush=True)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"chip_smoke_{report['compile']['state']}.json"),
            "w") as f:
        json.dump(report, f, indent=1)

    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) FAILED:",
              file=sys.stderr)
        for name in checks.failed:
            print(f"  - {name}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

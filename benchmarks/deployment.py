"""One running deployment from a configuration file: an ``Instance`` at
the file's settings, populated by the file's kind (its fleet, its
rules), with its watchdog calibrated — what an operator does before
traffic.  Copied from ``chip_smoke.py`` (drain) where that was sound."""

from __future__ import annotations

import os
import shutil
import tempfile
import time

from benchmarks import cells

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileMeter:
    """Programs XLA compiled (or loaded from the persistent cache), the
    seconds that took and the cache's hits and misses, from JAX's own
    monitoring events; ``take()`` returns the totals since the last."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self._s, self._n, self._hits, self._misses = 0.0, 0, 0, 0
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


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no memory statistics, as the CPU does)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class Deployment:
    """The system under test, started from ``config`` (a configuration
    file's content), with ``connector`` as its one priority outbound
    connector.  ``close()`` stops it and removes its data."""

    def __init__(self, config: dict, connector_fn, log=print) -> None:
        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.outbound.connectors import CallbackConnector
        from sitewhere_tpu.pipeline import packed
        from sitewhere_tpu.runtime.config import Config

        self.config = config
        self.kind = cells.load_kind(config)
        self.log = log
        self.copy_errors0 = packed.host_copy_errors
        self.tmp = tempfile.mkdtemp(prefix="sw-bench-")
        tree = {k: dict(v) for k, v in config["config"].items()}
        tree.setdefault("instance", {}).update(
            id="bench", data_dir=os.path.join(self.tmp, "data"))
        self.inst = Instance(Config(tree, apply_env=False))
        # priority: the overload ladder sheds bulk fan-out from SHEDDING
        # up; only the "always flows" class sees every stored row
        self.inst.outbound.add_connector(CallbackConnector(
            "bench-client", connector_fn, priority=True))
        t0 = time.perf_counter()
        self.inst.start()
        self.d = self.inst.dispatcher
        if self.d.warm_error is not None:
            raise RuntimeError(f"warm-up dispatch failed: "
                               f"{self.d.warm_error!r}")
        log(f"[deploy] instance started in {time.perf_counter() - t0:.1f}s")
        pipeline = self.inst.config.section("pipeline")
        self.width = int(pipeline["width"])
        self.capacity = int(pipeline["registry_capacity"])
        self.n_shards = int(pipeline["n_shards"])
        self.mtype_slots = int(pipeline["mtype_slots"])
        self.ring_depth = int(self.d.ring_depth)

    def populate(self) -> None:
        """The kind's fleet and rules, measurement name, watchdog
        calibration."""
        inst, config = self.inst, self.config
        self.kind.populate(self)
        self.mtype = int(inst.identity.mtype.mint(config["measurement"]))
        self.slot = self.mtype % self.mtype_slots
        # The watchdog's shipped budgets (1 s / 10 s) assume a ~10 ms
        # step; a deployment calibrates them from the instance's own
        # profile (PERF.md).  No threshold is weakened by hand.
        t0 = time.perf_counter()
        cal = config["calibration"]
        profile = inst.run_device_profile(iters=int(cal["iters"]),
                                          repeats=int(cal["repeats"]))
        wd = self.d.watchdog
        self.log(f"[deploy] device profile in {time.perf_counter() - t0:.1f}s:"
                 f" full_ms={profile.get('full_ms')} state_ms="
                 f"{profile.get('state_ms')}; watchdog soft {wd.soft_s:.2f}s"
                 f" hard {wd.hard_s:.2f}s")
        self.profile = profile

    def drain(self, timeout_s: float = 300.0) -> None:
        """flush() until the dispatcher is quiescent: nothing pending and
        the step count stopped moving (derived alerts re-enter the
        batcher, so one flush is not always the last); then the outbound
        queues."""
        deadline = time.monotonic() + timeout_s
        last = -1
        while time.monotonic() < deadline:
            self.d.flush(timeout_s=60.0)
            snap = self.d.metrics_snapshot()
            if snap["pending_rows"] == 0 and snap["steps"] == last:
                self.inst.outbound.drain(timeout=60.0)
                return
            last = snap["steps"]
        raise RuntimeError(f"dispatcher did not drain in {timeout_s:.0f}s")

    def marks(self) -> dict:
        """Every counter's value and every timer's (total seconds,
        count) now, for window deltas."""
        snap = self.inst.metrics.snapshot()
        out = dict(snap["counters"])
        for name, t in snap["timers"].items():
            out[name] = (t["mean_ms"] * t["count"] / 1e3, t["count"])
        out["_dispatcher"] = self.d.metrics_snapshot()
        # the dispatcher's own per-plan samples, newest last (one C-level
        # copy: nothing appends in the middle of it)
        out["_plan_latencies_s"] = list(self.d.latencies_s)
        return out

    def state_row(self, handle: int) -> dict:
        """One device's state in the reference's terms."""
        row = self.inst.device_state.get_device_state_by_id(int(handle))
        loc = row["last_location"]
        return {"last_event_ts_s": row["last_event_ts_s"],
                "last_event_type": row["last_event_type"],
                "value": row["last_values"][self.slot],
                "value_ts_s": row["last_value_ts_s"][self.slot],
                "lat": loc["lat"], "lon": loc["lon"],
                "loc_ts_s": loc["ts_s"]}

    def close(self) -> None:
        try:
            self.inst.stop()
            self.inst.terminate()
        finally:
            shutil.rmtree(self.tmp, ignore_errors=True)

"""Device-side telemetry: stage-time profiling + compiled-program cost.

Two complementary sources of on-device evidence feed the continuous-
profiling surface (the third — per-step occupancy counters — rides the
packed metrics vector itself, ``pipeline/packed.py TELEMETRY_SCALARS``):

1. **Stage-time probes** (:func:`profile_device_stages`): every probe
   is a ``lax.fori_loop`` chain inside ONE jit call so per-call
   dispatch amortizes away, inputs are perturbed by the loop
   index so XLA cannot hoist the work, the chain's result is FETCHED
   (the fetch cannot return before the work is done), and the measured
   trivial-program RTT is subtracted.  Samples land in
   ``device.stage_ms.<stage>`` histograms
   so repeated calibrations build a distribution an operator can read
   next to the host-side ``pipeline.stage_*_s`` timers.

   TPU programs have no readable clock, so "per-stage device
   timestamps" are necessarily measured this way — chained probes at
   the production width, on demand or at boot — rather than sampled
   inside the live program (which would cost a host sync per read,
   exactly what the ring exists to avoid).

2. **XLA cost analysis** (:func:`xla_cost_analysis`): flops / bytes
   accessed of a compiled program, recorded once as ``device.cost.*``
   gauges when the dispatcher's chain compiles — the static half of
   the roofline the stage probes measure dynamically.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger("sitewhere_tpu.telemetry")

# The probed stages, in pipeline order (keys of the result dict and the
# ``device.stage_ms.<stage>`` histogram family suffixes).
DEVICE_STAGES: Tuple[str, ...] = (
    "validate", "rules", "zones", "state", "full")

# Millisecond-scale buckets for the device stage histograms: the 7.9 ms
# device step and its sub-millisecond stages must not collapse into one
# bucket (the default latency buckets are seconds-denominated).
DEVICE_STAGE_MS_BUCKETS: Tuple[float, ...] = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
    50.0, 100.0)


# the trivial probe, compiled once per process: a fresh lambda per call
# would miss the jit cache and re-trace on every RTT measurement (5×
# per stage profile) — dead compile time the calibration need not pay
_TRIVIAL_PROBE = None


def measure_rtt(samples: int = 7) -> float:
    """Median round-trip of a trivial jitted fetch (seconds) — the
    per-sync floor the chain timings subtract.  The ONE probe the
    calibration library, bench, and the host-path tool share
    (methodology fixes land once, not per copy)."""
    global _TRIVIAL_PROBE
    import jax
    import jax.numpy as jnp

    if _TRIVIAL_PROBE is None:
        _TRIVIAL_PROBE = jax.jit(lambda x: x + 1)
    trivial = _TRIVIAL_PROBE
    int(trivial(jnp.int32(0)))  # warm (compiles only the first time)
    rtts = []
    for _ in range(samples):
        t0 = time.perf_counter()
        int(trivial(jnp.int32(0)))
        rtts.append(time.perf_counter() - t0)
    return float(np.median(rtts))


def profile_device_stages(width: int = 16_384, capacity: int = 16_384,
                          active: Optional[int] = None,
                          rules_capacity: int = 64,
                          zones_capacity: int = 64,
                          iters: int = 16, repeats: int = 3,
                          metrics=None) -> Dict[str, object]:
    """Measure per-stage DEVICE time for the fused pipeline step at the
    given width (callable from the instance and REST).

    Returns ``{"<stage>_ms": median_ms, ..., "host_rtt_ms": ...,
    "width": ..., "iters": ...}``.  When ``metrics`` (a
    ``MetricsRegistry``) is passed, every repeat's sample is observed
    into the ``device.stage_ms.<stage>`` histogram so calibrations
    accumulate into a scrapeable distribution.

    Cost: compiles one small chain per stage — seconds of one-time work,
    which is why this is an on-demand diagnostic (REST/bench/boot-knob),
    never part of the live dispatch path.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sitewhere_tpu.pipeline.step import (
        eval_threshold_rules,
        eval_zone_rules,
        pipeline_step,
        update_device_state,
        validate_and_enrich,
    )
    from sitewhere_tpu.schema import (
        DeviceState,
        EventBatch,
        Registry,
        RuleTable,
        ZoneTable,
    )

    active = min(capacity, active if active else max(1, width // 2))
    registry = Registry.empty(capacity).replace(
        active=jnp.arange(capacity) < active,
        assignment_status=jnp.ones(capacity, jnp.int32))
    state = DeviceState.empty(capacity)
    # rule/zone cost is SHAPE-driven under XLA (every slot evaluates,
    # active or not), so the probe tables must match the deployment's
    # table capacity or the rules/zones rows under-report production
    rules = RuleTable.empty(max(1, rules_capacity))
    zones = ZoneTable.empty(max(1, zones_capacity))
    rng = np.random.default_rng(0)
    batch = EventBatch.empty(width).replace(
        valid=jnp.ones(width, bool),
        device_id=jnp.asarray(
            rng.integers(0, active, width).astype(np.int32)),
        ts_s=jnp.full(width, 1_753_800_000, jnp.int32),
        value=jnp.asarray(rng.uniform(0, 100, width).astype(np.float32)),
        update_state=jnp.ones(width, bool),
    )
    jax.block_until_ready(batch)

    def pb(i):
        # perturb by the loop index or XLA hoists the loop-invariant
        # work and the probe measures an empty chain
        i = jnp.int32(i)
        return batch.replace(
            device_id=(batch.device_id + i) % active,
            ts_s=batch.ts_s + i,
            value=batch.value + i.astype(jnp.float32) * 1e-6,
        )

    def chain_ms(body, carry0):
        @jax.jit
        def chain(c):
            return lax.fori_loop(0, iters, body, c)

        out = chain(carry0)
        jax.tree.map(lambda x: x.block_until_ready(), out)  # compile
        rtt = measure_rtt()
        samples = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            out = chain(carry0)
            # fetch the scalar accumulator: the timed region ends when
            # the value is on the host
            float(np.asarray(jax.tree.leaves(out)[-1]).reshape(-1)[0])
            samples.append(
                max(0.0, time.perf_counter() - t0 - rtt) / iters * 1e3)
        return samples, rtt

    def b_validate(i, acc):
        a, _, _, e = validate_and_enrich(registry, pb(i))
        return acc + a.sum(dtype=jnp.int32) + e["area_id"].sum()

    def b_rules(i, c):
        st, acc = c
        bt = pb(i)
        a, _, _, _ = validate_and_enrich(registry, bt)
        f, rid, ew = eval_threshold_rules(rules, st, bt, a)
        return (st, acc + f.sum(dtype=jnp.int32) + rid.sum()
                + ew.sum().astype(jnp.int32))

    def b_zones(i, acc):
        bt = pb(i)
        a, _, _, e = validate_and_enrich(registry, bt)
        f, zid = eval_zone_rules(zones, bt, a, e["area_id"])
        return acc + f.sum(dtype=jnp.int32) + zid.sum()

    def b_state(i, c):
        st, acc = c
        bt = pb(i)
        st2, present = update_device_state(st, bt, bt.valid)
        return (st2, acc + st2.last_event_ts_s.sum()
                + present.sum(dtype=jnp.int32))

    def b_full(i, c):
        st, acc = c
        st2, out = pipeline_step(registry, st, rules, zones, pb(i))
        # fold EVERY output leg into the carry or XLA dead-code-
        # eliminates the rules/geofence/enrichment work
        return (st2, acc + out.metrics.accepted + out.rule_id.sum()
                + out.zone_id.sum() + out.assignment_id.sum()
                + out.derived_alerts.alert_code.sum()
                + out.present_now.sum(dtype=jnp.int32))

    probes = {
        "validate": (b_validate, jnp.int32(0)),
        "rules": (b_rules, (state, jnp.int32(0))),
        "zones": (b_zones, jnp.int32(0)),
        "state": (b_state, (state, jnp.int32(0))),
        "full": (b_full, (state, jnp.int32(0))),
    }
    result: Dict[str, object] = {"width": width, "iters": iters,
                                 "repeats": repeats}
    rtt_s = 0.0
    for stage, (body, carry0) in probes.items():
        samples, rtt_s = chain_ms(body, carry0)
        result[f"{stage}_ms"] = round(float(np.median(samples)), 4)
        if metrics is not None:
            hist = metrics.histogram(f"device.stage_ms.{stage}",
                                     buckets=DEVICE_STAGE_MS_BUCKETS)
            for s in samples:
                hist.observe(s)
    result["host_rtt_ms"] = round(rtt_s * 1e3, 4)
    if result.get("full_ms"):
        result["device_events_per_s"] = round(
            width / float(result["full_ms"]) * 1e3, 1)
    return result


def xla_cost_analysis(fn, *args) -> Optional[Dict[str, float]]:
    """Flops / bytes of ``fn`` compiled for ``args`` (an already-jitted
    callable).  Returns ``{"flops": ..., "bytes_accessed": ...}`` plus
    any other numeric keys XLA reports, or None when the backend/JAX
    build doesn't support cost analysis — never raises (this is
    best-effort evidence, not a dependency of the dispatch path)."""
    try:
        compiled = fn.lower(*args).compile()
        cost = compiled.cost_analysis()
        if not cost:
            return None
        out: Dict[str, float] = {}
        for key, value in cost.items():
            if isinstance(value, (int, float)):
                out[key.replace(" ", "_")] = float(value)
        return out or None
    except Exception:
        logger.debug("XLA cost analysis unavailable", exc_info=True)
        return None


def record_cost_metrics(metrics, cost: Optional[Dict[str, float]],
                        prefix: str = "device.cost") -> None:
    """Record a cost-analysis dict as ``<prefix>.<key>`` gauges (the
    flops/bytes of the compiled chain, scraped next to the live stage
    timers).  No-op on None."""
    if not cost or metrics is None:
        return
    for key in ("flops", "bytes_accessed"):
        if key in cost:
            metrics.gauge(f"{prefix}.{key}").set(cost[key])


__all__ = [
    "DEVICE_STAGES", "DEVICE_STAGE_MS_BUCKETS", "measure_rtt",
    "profile_device_stages", "xla_cost_analysis", "record_cost_metrics",
]

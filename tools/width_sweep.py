"""Device-side packed-step latency vs batch width (config-1 phase-C
methodology via bench.py's SHARED helpers — packed_chain + measure_rtt —
so the sweep always measures exactly what the bench measures), extended
with the per-stage host attribution the device-resident dispatch loop is
judged by: for every width it also times the H2D slot staging
(``device_put`` of one packed batch), the blocking D2H output fetch, and
derives the per-batch host-sync budget — step_ms is the device dwell, and
``rtt/K + h2d + d2h`` is what a ring slot actually adds on the host side.
Run on any backend; widths via argv.

    python tools/width_sweep.py [width ...]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import bench  # noqa: E402
from sitewhere_tpu.pipeline.packed import (  # noqa: E402
    pack_batch_host,
    pack_state,
    pack_tables,
)

print("backend:", jax.default_backend(), flush=True)
capacity, n_active = 16384, 10000
chain_k = 64
n_batches = 4
registry, state, rules, zones = bench.build_tables(capacity, n_active)
tables = jax.jit(pack_tables)(registry, rules, zones)
pack_state_fn = jax.jit(pack_state)  # one jit wrapper: state is
# width-independent, so every width reuses the same compiled pack

rtt = bench.measure_rtt()
print(f"rtt_ms={rtt*1e3:.1f}", flush=True)


def _median(fn, n=3):
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


widths = tuple(int(a) for a in sys.argv[1:]) or (
    4_096, 16_384, 131_072, 262_144)
for width in widths:
    try:
        raw = bench.host_batches(width, n_active, n_batches=n_batches)
        packed = [pack_batch_host(b, width) for b in raw]

        # H2D stage: device_put of one packed (bi, bf) pair — the ring
        # slot fill the double-buffered path hides behind compute
        def h2d_once(pair=packed[0]):
            jax.block_until_ready(tuple(jax.device_put(a) for a in pair))

        h2d_once()
        h2d_ms = _median(h2d_once) * 1e3

        staged = [tuple(jax.device_put(a) for a in pair) for pair in packed]
        jax.block_until_ready(staged)
        carry = pack_state_fn(state)
        chain = bench.packed_chain(tables, staged, chain_k)
        carry, probe = chain(carry)
        int(probe)  # compile + settle
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            carry, probe = chain(carry)
            int(probe)
            # same clamp as bench.py phase C: on a co-located backend
            # the whole chain can finish in under one startup-probe RTT
            dt = max(0.0, time.perf_counter() - t0 - rtt)
            step_ms = dt / chain_k * 1e3
            if best is None or step_ms < best:
                best = step_ms

        # D2H fetch: one step's output block + metrics, fresh buffers
        # per sample (jax caches a fetched array's host copy)
        from sitewhere_tpu.pipeline.packed import packed_pipeline_step

        step = jax.jit(packed_pipeline_step)
        d2h_samples = []
        for _ in range(3):
            _, oi, mets, _present = step(tables, carry, *staged[0])
            jax.block_until_ready(mets)
            t0 = time.perf_counter()
            jax.device_get((oi, mets))
            d2h_samples.append(time.perf_counter() - t0)
        d2h_samples.sort()
        d2h_ms = d2h_samples[1] * 1e3

        # per-batch host cost of a K-deep ring slot: one dispatch+fetch
        # RTT amortized over K, plus this slot's own h2d and its share
        # of the chain's stacked d2h
        ring_host_ms = rtt * 1e3 / chain_k + h2d_ms + d2h_ms
        if best > 0:
            print(f"width={width} step_ms={best:.3f} "
                  f"device_eps={width/best*1e3/1e6:.2f}M "
                  f"h2d_ms={h2d_ms:.3f} d2h_ms={d2h_ms:.3f} "
                  f"ring_host_ms_per_batch={ring_host_ms:.3f} "
                  f"host_syncs_per_batch={1.0/chain_k:.4f}", flush=True)
        else:
            print(f"width={width} step_ms<rtt (chain faster than the "
                  f"RTT probe resolution) h2d_ms={h2d_ms:.3f} "
                  f"d2h_ms={d2h_ms:.3f}", flush=True)
        del staged, carry, chain
    except Exception as e:
        print(f"width={width} FAILED: {type(e).__name__}: {str(e)[:200]}",
              flush=True)

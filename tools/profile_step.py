"""On-chip profiler for the fused pipeline step and its stages.

The measurement methodology — fori-chain probes inside one jit call,
loop-index input perturbation so XLA cannot hoist the work, a FETCHED
result, and median-RTT subtraction — lives in
:mod:`sitewhere_tpu.pipeline.telemetry` (``profile_device_stages``),
where the instance's on-demand calibration endpoint and ``bench.py``
config-2 share it.  This tool is the CLI
front-end over that ONE implementation, so bench evidence and the
production ``device.stage_ms.*`` histograms can never measure different
things.

Usage::

    python tools/profile_step.py              # default backend (TPU)
    python tools/profile_step.py --cpu        # forced CPU
    python tools/profile_step.py --width 16384

Prints one line per stage: validate+enrich, threshold rules, zone rules
(geofence), state update, and the full step, plus derived events/s.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STAGE_LABELS = (
    ("validate", "validate+enrich"),
    ("rules", "threshold rules"),
    ("zones", "zone rules (geofence)"),
    ("state", "state update"),
    ("full", "FULL pipeline step"),
)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true",
                        help="force the CPU backend (config API, not env)")
    parser.add_argument("--width", type=int, default=131_072)
    parser.add_argument("--capacity", type=int, default=16_384)
    parser.add_argument("--active", type=int, default=10_000)
    parser.add_argument("--iters", type=int, default=64)
    parser.add_argument("--repeats", type=int, default=3,
                        help="timed chain runs per stage (median)")
    args = parser.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from sitewhere_tpu.pipeline.telemetry import profile_device_stages

    print(f"backend={jax.default_backend()} width={args.width} "
          f"capacity={args.capacity} iters={args.iters}")
    result = profile_device_stages(
        width=args.width, capacity=args.capacity, active=args.active,
        iters=args.iters, repeats=args.repeats)
    rtt_ms = result["host_rtt_ms"]
    for stage, label in STAGE_LABELS:
        print(f"{label:<24} {result[f'{stage}_ms']:8.3f} ms/iter   "
              f"(rtt {rtt_ms:.1f} ms)")
    if result.get("device_events_per_s"):
        print(f"device-side rate: {result['device_events_per_s']:,.0f} "
              "events/s")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""One run of one benchmark cell on the TPU.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and every metric are found
by name from BENCHMARK.json and the files beside this one
(``cells.py``); nothing here knows any of them.  One process; it exits
non-zero and prints no result unless JAX reports a TPU with the chips
the cell asks for.  The last line of standard output is the result:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
(and ``breakdown``) with ``--trace 1``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args()

    sys.path.insert(0, REPO)
    from benchmarks import cells, harness

    cell = cells.resolve_cell(args.workload)

    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)

    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_PROCESS,
        log=lambda line: print(line, flush=True))
    if result is None:
        return 1
    # the deployment is closed: these are the last lines on standard error
    for name, (got, limit) in result["compared"].items():
        print(f"compared {name}: {got} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

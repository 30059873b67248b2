#!/usr/bin/env python3
"""The knee of an open-loop cell: the highest of a few fixed rates the
deployment sustains.  Run once per such cell on the chip; the table goes
into PERF.md and four fifths of the knee into ``cells/<cell>.json``.

    python3 benchmarks/sweep.py --workload <cell> --rates 15000,25000,40000

The window is the benchmark's own ``run_seconds`` unless ``--seconds``
says otherwise, so that it holds what a run of the cell holds (the 30 s
checkpoint: a shorter window found knees the cells could not keep).

A rate is sustained when no send was shed, dead-lettered or left
unsent, every event was delivered by the end of the final drain, and the
undelivered backlog (events due - events delivered) is not growing: its
median over the second half of the window is no larger than over the
first half, give or take what is in flight at any instant (two
payloads, or half a percent of the window's events where that is more).
Medians of 20 instants each, because a checkpoint that holds the
interpreter for seconds near the window's end is a stall (it shows in
p99), not a queue that grows.  One process, one fresh deployment per
rate; each line of the table is also written to
``chiprun_out/sweep-<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog(run, at: float) -> int:
    """Events due by ``at`` seconds into the window and not delivered."""
    log, t = run.sends, run.t_begin + at
    due = int(log.n[log.measured & (log.due <= t)].sum())
    got = int(sum(c[log.measured[s]].sum()
                  for when, s, c in run.delivery.rows if when <= t))
    return due - got


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated events/s, rising")
    p.add_argument("--seconds", type=float)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    sys.path.insert(0, REPO)
    from benchmarks import cells, harness

    if args.seconds is None:
        args.seconds = float(cells.load_benchmark()["run_seconds"])

    harness.enable_compile_cache()
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        cell = cells.resolve_cell(args.workload)
        cell["traffic"]["rate_events_per_s"] = rate
        seen = {}
        result = harness.run_cell(
            cell, args.seed, args.seconds, False, time.perf_counter(),
            log=lambda line: print(line, flush=True),
            on_run=lambda run: seen.update(run=run))
        if result is None:
            return 1
        run = seen["run"]
        status = np.bincount(run.sends.status[run.sends.measured],
                             minlength=4)
        slack = max(2 * int(cell["traffic"]["lines_per_payload"]),
                    0.005 * result["attempted"])
        at = np.linspace(0.0, args.seconds, 41)[1:]
        logs = [backlog(run, t) for t in at]
        b_first = float(np.median(logs[:20]))
        b_second = float(np.median(logs[20:]))
        made = run.sends.measured & (run.sends.status > 0)
        row = {
            "rate": rate, "seconds": args.seconds,
            "delivered_per_s": result["metrics"]["events_per_s"]["value"],
            "p50_ms": run.latency_percentile_ms(50),
            "p99_ms": run.latency_percentile_ms(99),
            "shed": int(status[harness.SHED]),
            "unsent": int(status[harness.UNSENT]),
            "failed": result["failed"], "correct": result["correct"],
            "backlog_first_half": b_first, "backlog_second_half": b_second,
            "backlog_end": logs[-1],
            "gen_late_p99_ms": float(np.percentile(
                run.sends.sent[made] - run.sends.due[made], 99) * 1e3),
            "not_normal_share": float((run.overload_states > 0).mean()),
            "sustained": bool(status[harness.SHED] == 0
                              and status[harness.UNSENT] == 0
                              and result["failed"] == 0
                              and b_second <= b_first + slack),
        }
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
        with open(os.path.join(out_dir, f"sweep-{args.workload}.jsonl"),
                  "a") as f:
            f.write(json.dumps(row) + "\n")
    held = [r["rate"] for r in rows if r["sustained"]]
    knee = max(held) if held else None
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "four_fifths": 0.8 * knee if knee else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

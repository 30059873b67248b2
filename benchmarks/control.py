#!/usr/bin/env python3
"""The control: one run of a cell with a stated guarantee broken
underneath, which has to come out ``correct: false``.

    python3 benchmarks/control.py --workload <cell> --seed <n> --seconds <s> --fault <name>

This system runs no model and states no precision, so the control
breaks one guarantee the configuration states.  Which guarantees, how
each is broken and which comparison then has to fail is the
configuration's kind's to say (its ``FAULTS``; a name it does not have
prints them): the rest of the run is the benchmark's own, at the cell's
own size.  The benchmark's runs never come here; ``tests/test_faults.py``
does, at toy size, and a builder does on the chip.  Exits 0 when the
comparison saw the fault, 1 when it did not (or no chip was found), and
prints the comparisons that failed.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def broken(deployment, when: str, plant):
    """``deployment`` (the harness's class) with ``plant(dep)`` run
    ``when`` ("before" or "after") it is populated; the reference keeps
    the configuration as its file states it."""

    class Broken(deployment):
        def populate(self):
            stated = self.config
            if when == "before":
                self.config = copy.deepcopy(stated)
                plant(self)
            super().populate()
            self.config = stated
            if when == "after":
                plant(self)

    return Broken


def failed_comparisons(result: dict) -> list:
    return [name for name, (got, limit) in result["compared"].items()
            if got != limit]


def run_control(cell: dict, fault: str, seed: int, seconds: float,
                t_process: float, require_tpu: bool = True, log=print):
    """One run of ``cell`` with ``fault`` of its kind underneath: what
    the run read and whether the comparison the kind names saw it, or
    None where no chip was found.  KeyError, with the kind's faults in
    its message, for a fault the kind does not have."""
    from benchmarks import cells, harness

    faults = cells.load_kind(cell["config"]).FAULTS
    if fault not in faults:
        raise KeyError(f"kind {cell['config']['kind']!r} has no fault "
                       f"{fault!r}; it has: {', '.join(sorted(faults))}")
    when, plant, must = faults[fault]
    sound = harness.Deployment
    harness.Deployment = broken(sound, when, plant)
    try:
        result = harness.run_cell(cell, seed, seconds, False, t_process,
                                  require_tpu=require_tpu, log=log)
    finally:
        harness.Deployment = sound
    if result is None:
        return None
    failed = failed_comparisons(result)
    seen = (result["correct"] is False
            and any(name.startswith(must) for name in failed))
    return {"control": fault, "workload": cell["name"], "seed": seed,
            "correct": result["correct"], "seen": seen, "must_fail": must,
            "failed_comparisons": {n: result["compared"][n] for n in failed},
            "compared": len(result["compared"]),
            "attempted": result["attempted"], "failed": result["failed"]}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fault", required=True,
                   help="a fault of the cell's kind")
    p.add_argument("--no-chip", action="store_true",
                   help="do not look for the chip (the CPU, a toy cell)")
    args = p.parse_args()

    sys.path.insert(0, REPO)
    from benchmarks import cells, harness

    cell = cells.resolve_cell(args.workload)
    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
    try:
        doc = run_control(cell, args.fault, args.seed, args.seconds,
                          T_PROCESS, require_tpu=not args.no_chip,
                          log=lambda line: print(line, flush=True))
    except KeyError as e:
        print(e.args[0])
        return 2
    if doc is None:
        return 1
    print(json.dumps(doc), flush=True)
    return 0 if doc["seen"] else 1


if __name__ == "__main__":
    sys.exit(main())

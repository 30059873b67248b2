"""The least time one pipeline step can take on a chip, from shapes.

What the step has to do for a batch of B rows against a registry of D
devices with M measurement slots, whatever the implementation:

- read the batch (16 words a row: 12 int32 + 4 float32, the packed
  plan) and write the packed outputs (10 int32 a row);
- look up each row's registry entry and read and write the state rows
  it touches: per row the device's last-event block, its location
  block and one measurement slot — read once, written once;
- evaluate every rule and every zone on every row.

It does NOT have to touch the D*M slots no row names; a step that does
(the gather-at-capacity state update, PERF.md) shows as a small share.
Peaks come from ``peaks.json`` by ``device_kind``; a kind that is not
in the table is an error, never a default.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")

BATCH_WORDS_IN = 16        # [12, B] int32 + [4, B] float32
BATCH_WORDS_OUT = 10       # [10, B] int32
REGISTRY_WORDS = 8         # active, assignment, type, area, customer, ...
STATE_WORDS = 3 + 5 + 3    # last event (s, ns, type); location; one slot
OPS_PER_RULE = 4           # select by mtype, compare, and, count
OPS_PER_ZONE_VERTEX = 10   # one edge crossing test


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have: {', '.join(sorted(table))})")
    return table[device_kind]


def step_bytes(width: int) -> float:
    """Bytes one step must move to and from HBM for ``width`` rows."""
    words = (BATCH_WORDS_IN + BATCH_WORDS_OUT + REGISTRY_WORDS
             + 2 * STATE_WORDS)
    return 4.0 * words * width


def step_ops(width: int, rules: int, zones: int, vertices: int) -> float:
    """Arithmetic operations one step must make for ``width`` rows."""
    return float(width) * (rules * OPS_PER_RULE
                           + zones * vertices * OPS_PER_ZONE_VERTEX)


def rule_shape(rules: dict) -> dict:
    """The counts ``step_ops`` takes, from a configuration's ``rules``
    (threshold rules, and zones that are rectangles: four vertices)."""
    return {"rules": len(rules["thresholds"]), "zones": len(rules["zones"]),
            "vertices": 4}


def step_floor(device_kind: str, width: int, rules: int, zones: int,
               vertices: int) -> dict:
    """Least seconds for one step on one chip, and which peak bounds."""
    peaks = peaks_for(device_kind)
    by_bytes = step_bytes(width) / peaks["hbm_bytes_per_s"]
    by_ops = step_ops(width, rules, zones, vertices) / peaks["f32_flops"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "bandwidth" if by_bytes >= by_ops else "compute",
            "bytes": step_bytes(width),
            "ops": step_ops(width, rules, zones, vertices)}

"""From a profiler trace (``.xplane.pb``) to the device's numbers.

``load`` reads the file with nothing but JAX into plain tuples;
everything after works on those, so the arithmetic is checked against a
small recorded trace (``tests/data``) without a chip.

- busy: on each device plane, the union of the intervals in which an
  operation ran (the ``XLA Ops`` line), clipped to the traced window;
  ``busy_s`` is the mean over the chips, the idle share is taken on the
  chip that was busy least.
- step time: the device duration of each run of a step program (the
  ``XLA Modules`` line), matched by the name patterns the configuration
  file gives, divided by the steps one run makes (a chain makes
  ``ring_depth``).
- gaps: the idle intervals of the first chip.  The part that lies
  inside a program's run (between its operations) is named so; the
  rest is the host's: each of the ``NAMED_GAPS`` longest gaps is named
  by the host span that covered most of it — the harness's own
  ``bench.*`` annotations first, then any other host event with its
  thread — and the shorter ones are summed under one name.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.trace_window"
TOP = 10
NAMED_GAPS = 200
IN_PROGRAM = "inside a program run (between its operations)"
SHORTER = "shorter gaps (not named)"


def load(path: str) -> list:
    """[(plane name, [(line name, [(event name, start_ns, dur_ns)])])]"""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def union(intervals: list) -> list:
    """Sorted, disjoint [(start, end)] covering the same points."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of disjoint sorted ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for start, end in busy:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def _line(lines: list, name: str) -> list:
    for line_name, events in lines:
        if line_name == name:
            return events
    return []


def _host_spans(planes: list) -> tuple:
    """(labels, starts, ends, is_bench) of every host event but the
    window's own span; and that span as (start, end) or None."""
    labels, starts, ends, bench, window = [], [], [], [], None
    for plane_name, lines in planes:
        if not plane_name.startswith(HOST_PLANE):
            continue
        for thread, events in lines:
            for name, start, dur in events:
                if name == WINDOW_SPAN:
                    window = (start, start + dur)
                    continue
                is_bench = name.startswith("bench.")
                labels.append(name if is_bench else f"{thread}: {name}")
                starts.append(start)
                ends.append(start + dur)
                bench.append(is_bench)
    return (labels, np.asarray(starts, float), np.asarray(ends, float),
            np.asarray(bench, bool)), window


def _name_gap(gap: tuple, spans: tuple) -> str:
    labels, starts, ends, bench = spans
    if not labels:
        return "no host span"
    cover = np.minimum(ends, gap[1]) - np.maximum(starts, gap[0])
    for mask in (bench, ~bench):
        if mask.any():
            at = int(np.argmax(np.where(mask, cover, -np.inf)))
            if cover[at] > 0:
                return labels[at]
    return "no host span"


def _short(op: str) -> str:
    """``%fusion.2 = f32[16384,4]{...} fusion(...)`` → ``%fusion.2
    f32[16384,4]``: the HLO name and the shape it produces."""
    name, _, rest = op.partition(" = ")
    return f"{name} {rest.split('{')[0]}".strip()[:80]


def _top(totals: dict) -> list:
    return [[name, seconds] for name, seconds in sorted(
        totals.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce(planes: list, programs: dict, ring_depth: int) -> dict:
    """The trace's numbers, or {} when no operation ran on a device.
    ``programs``: name pattern → steps one run makes (a number, or
    ``"ring_depth"``)."""
    devices = sorted((int(DEVICE_PLANE.match(name).group(1)), lines)
                     for name, lines in planes if DEVICE_PLANE.match(name))
    spans, window = _host_spans(planes)
    ops_of = {chip: _line(lines, OPS_LINE) for chip, lines in devices}
    if not any(ops_of.values()):
        return {}
    if window:
        lo, hi = window
    else:
        lo = min(s for ops in ops_of.values() for _, s, _ in ops)
        hi = max(s + d for ops in ops_of.values() for _, s, d in ops)
    busy_of = {chip: clip(union([(s, s + d) for _, s, d in ops]), lo, hi)
               for chip, ops in ops_of.items()}
    busy_s = {chip: sum(e - s for s, e in b) / 1e9
              for chip, b in busy_of.items()}
    window_s = (hi - lo) / 1e9

    op_totals: dict = {}
    short: dict = {}
    for ops in ops_of.values():
        for name, start, dur in ops:
            if start + dur > lo and start < hi:
                key = short.get(name) or short.setdefault(name, _short(name))
                op_totals[key] = op_totals.get(key, 0.0) \
                    + dur / 1e9 / len(devices)

    first_chip, first = devices[0]
    step_ms, runs = [], {}
    for name, start, dur in _line(first, MODULES_LINE):
        for pattern, steps in programs.items():
            if re.search(pattern, name):
                k = ring_depth if steps == "ring_depth" else int(steps)
                step_ms += [dur / 1e6 / max(k, 1)] * max(k, 1)
                runs[pattern] = runs.get(pattern, 0) + 1
                break
    step_ms.sort()

    idle = gaps(busy_of[first_chip], lo, hi)
    running = clip(union(
        [(s, s + d) for _, s, d in _line(first, MODULES_LINE)]
        + busy_of[first_chip]), lo, hi)
    host_gaps = sorted(gaps(running, lo, hi), key=lambda g: g[0] - g[1])
    gap_totals: dict = {}
    for gap in host_gaps[:NAMED_GAPS]:
        label = _name_gap(gap, spans)
        gap_totals[label] = gap_totals.get(label, 0.0) \
            + (gap[1] - gap[0]) / 1e9
    rest = sum(e - s for s, e in host_gaps[NAMED_GAPS:]) / 1e9
    if rest:
        gap_totals[SHORTER] = rest
    inside = (sum(e - s for s, e in idle)
              - sum(e - s for s, e in host_gaps)) / 1e9
    if inside > 0:
        gap_totals[IN_PROGRAM] = inside
    return {
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "window_s": window_s,
        "idle_share_worst": 1.0 - min(busy_s.values()) / window_s,
        "device_step_ms": step_ms[len(step_ms) // 2] if step_ms else None,
        "program_runs": runs,
        "device_ops": _top(op_totals),
        "idle_gaps": _top(gap_totals),
    }


def reduce_dir(trace_dir: str, programs: dict, ring_depth: int) -> dict:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return {}
    return reduce(load(found[0]), programs, ring_depth)

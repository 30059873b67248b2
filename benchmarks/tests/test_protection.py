"""The two readers of the overload ladder's 100 ms samples, against
hand-made windows: ``not_normal_share`` (any rung above NORMAL) and
``emergency_share`` (the top rung: which regime an overloaded run was
in)."""

import os

import numpy as np
import pytest

from benchmarks import cells
from benchmarks.harness import Run

NORMAL, DEGRADED, SHEDDING, EMERGENCY = 0, 1, 2, 3
CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]

# window -> (not_normal_share, emergency_share)
WINDOWS = {
    "quiet": ([NORMAL] * 10, (0.0, 0.0)),
    "shedding all window": ([SHEDDING] * 10, (100.0, 0.0)),
    "emergency after a save": ([DEGRADED] + [SHEDDING] * 5 + [EMERGENCY] * 4,
                               (100.0, 40.0)),
    "a dip and back": ([NORMAL] * 6 + [DEGRADED] * 2 + [NORMAL] * 2,
                       (20.0, 0.0)),
    "emergency throughout": ([EMERGENCY] * 3, (100.0, 100.0)),
}


def read(metric, states):
    return cells.reader("layer_metrics", metric)(
        Run(overload_states=np.asarray(states, np.int64)))


@pytest.mark.parametrize("window", sorted(WINDOWS))
@pytest.mark.parametrize("which, metric", enumerate(
    ["not_normal_share", "emergency_share"]))
def test_share_of_the_windows_samples(window, which, metric):
    states, want = WINDOWS[window]
    assert read(metric, states) == pytest.approx(want[which])


@pytest.mark.parametrize("metric", ["not_normal_share", "emergency_share"])
def test_none_with_no_samples(metric):
    assert read(metric, []) is None


def test_the_rung_is_the_programs_emergency():
    from sitewhere_tpu.runtime.overload import OverloadState

    mod = cells.load_module(os.path.join(
        cells.HERE, "layer_metrics", "emergency_share.py"))
    assert mod.EMERGENCY == int(OverloadState.EMERGENCY) == max(
        int(s) for s in OverloadState)


@pytest.mark.parametrize("metric", ["not_normal_share", "emergency_share"])
def test_the_entry_lists_every_cell_and_moves_the_rate(metric):
    entry = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}[metric]
    assert entry["workloads"] == CELLS
    assert (entry["layer"], entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("protection", "%", "lower",
                                "program_counter", "events_per_s")

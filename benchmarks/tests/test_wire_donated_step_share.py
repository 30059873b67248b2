"""``wire_donated_step_share`` against hand-made run records: the share,
None on a window without a step, and None on a program without the
counter (the parent of the PR that brought it)."""

import pytest

from benchmarks import cells
from benchmarks.harness import Run

DONATED = "pipeline.steps_donated"


def read(steps0, steps1, counters0, counters1):
    return cells.reader("layer_metrics", "wire_donated_step_share")(Run(
        marks0={**counters0, "_dispatcher": {"steps": steps0}},
        marks1={**counters1, "_dispatcher": {"steps": steps1}}))


@pytest.mark.parametrize("donated, want", [(0, 0.0), (30, 37.5),
                                           (80, 100.0)])
def test_share_of_the_windows_steps(donated, want):
    assert read(20, 100, {DONATED: 7}, {DONATED: 7 + donated}) \
        == pytest.approx(want)


def test_none_on_zero_steps():
    assert read(20, 20, {DONATED: 7}, {DONATED: 7}) is None


def test_none_where_the_program_has_no_counter():
    assert read(20, 100, {}, {}) is None

"""``wire_idle_emit_share`` against hand-made run records: the share,
None on a window without a wire row, and None on a program without the
counter (one older than the idle emission)."""

import pytest

from benchmarks import cells
from benchmarks.harness import Run

IDLE, ALL = "ingest.rows_emitted_idle", "ingest.wire_rows"


def read(counters0, counters1):
    return cells.reader("layer_metrics", "wire_idle_emit_share")(Run(
        marks0={**counters0, "_dispatcher": {}},
        marks1={**counters1, "_dispatcher": {}}))


@pytest.mark.parametrize("idle, want", [(0, 0.0), (768, 75.0),
                                        (1024, 100.0)])
def test_share_of_the_windows_wire_rows(idle, want):
    assert read({IDLE: 4096, ALL: 8192},
                {IDLE: 4096 + idle, ALL: 8192 + 1024}) == pytest.approx(want)


def test_none_on_no_wire_row():
    assert read({IDLE: 5, ALL: 10}, {IDLE: 5, ALL: 10}) is None


def test_none_where_the_program_has_no_counter():
    assert read({ALL: 10}, {ALL: 2058}) is None

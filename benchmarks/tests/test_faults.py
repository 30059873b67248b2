"""The comparison that decides ``correct`` is shown to fail: a whole toy
run with the served path broken underneath, once for each fault of the
cell's kind (``FAULTS`` of ``configs/kinds/<kind>.py``: the control and
the faults a cell can have; the same planters a builder runs on the chip
at the cell's own size through ``benchmarks/control.py``).  The chip is
not looked for (``require_tpu=False``); the rest of the run is the
benchmark's own.
"""

import time

import pytest

from benchmarks import cells, control
from benchmarks.tests.toy import toy_cell, toy_run

CELL = "fleet-1m.columns-saturate"
FAULTS = cells.load_kind(cells.resolve_cell(CELL)["config"]).FAULTS


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_served_path_reads_not_correct(fault):
    doc = control.run_control(toy_cell(CELL), fault, 3, 1.0,
                              time.perf_counter(), require_tpu=False)
    assert doc["correct"] is False
    assert doc["seen"], (doc["must_fail"], doc["failed_comparisons"])


def test_the_same_run_unbroken_reads_correct():
    result, run = toy_run(CELL, seconds=1.0)
    assert result["correct"] is True
    assert control.failed_comparisons(result) == []

"""The comparison that decides ``correct`` is shown to fail: a whole toy
run with the served path broken underneath, once for each fault of
``benchmarks/control.py`` (the control and the faults a cell can have;
the same planters a builder runs on the chip at the cell's own size).
The chip is not looked for (``require_tpu=False``); the rest of the run
is the benchmark's own.
"""

import pytest

from benchmarks import control, harness
from benchmarks.tests.toy import toy_run


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_a_broken_served_path_reads_not_correct(fault, monkeypatch):
    monkeypatch.setattr(harness, "Deployment",
                        control.broken(harness.Deployment, fault))
    result, run = toy_run("fleet-1m.columns-saturate", seconds=1.0)
    assert result["correct"] is False
    failed = control.failed_comparisons(result)
    assert any(name.startswith(control.FAULTS[fault][2])
               for name in failed), failed


def test_the_same_run_unbroken_reads_correct():
    result, run = toy_run("fleet-1m.columns-saturate", seconds=1.0)
    assert result["correct"] is True
    assert control.failed_comparisons(result) == []

"""The seal's two readers against hand-made run records: the share of
seals the native writer made, the mean seal job, None on a program
without the counter or timer (one older than the native seal) and on a
window without a seal, and both entries naming the columns cells."""

import pytest

from benchmarks import cells
from benchmarks.harness import Run

SEALS, SEALS_NATIVE, SEAL_JOB = \
    "store.seals", "store.seals_native", "store.seal_job_s"
MARKS0 = {SEALS: 10, SEALS_NATIVE: 9, SEAL_JOB: (0.02, 10)}
MARKS1 = {SEALS: 50, SEALS_NATIVE: 49, SEAL_JOB: (0.10, 50)}
EXPECTED = {
    "native_seal_share": 100.0 * (49 - 9) / (50 - 10),
    "seal_ms_per_segment": (0.10 - 0.02) / 40 * 1e3,
}
# what each metric cannot do without
NEEDS = {"native_seal_share": SEALS_NATIVE,
         "seal_ms_per_segment": SEAL_JOB}


def read(metric, marks0=MARKS0, marks1=MARKS1):
    return cells.reader("layer_metrics", metric)(
        Run(marks0=marks0, marks1=marks1))


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_seal_reader_arithmetic(metric):
    assert read(metric) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_seal_readers_read_nothing_without_the_program_or_a_seal(metric):
    """The parent commit keeps neither; a window without a seal."""
    gone = NEEDS[metric]
    marks0 = {k: v for k, v in MARKS0.items() if k != gone}
    marks1 = {k: v for k, v in MARKS1.items() if k != gone}
    assert read(metric, marks0, marks1) is None
    assert read(metric, MARKS1, MARKS1) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_seal_entries_have_their_reader_and_the_columns_cells(metric):
    entry = {m["name"]: m
             for m in cells.load_benchmark()["per_layer"]}[metric]
    assert callable(cells.reader("layer_metrics", metric))
    assert (entry["layer"], entry["moves"], entry["workloads"]) == (
        "D2H and egress", "events_per_s",
        ["fleet-1m.columns-saturate", "fleet-4m-mesh4.columns-saturate"])
    assert (entry["source"], entry["unit"], entry["better"]) == {
        "native_seal_share": ("program_counter", "%", "higher"),
        "seal_ms_per_segment": ("program_span", "ms", "lower"),
    }[metric]

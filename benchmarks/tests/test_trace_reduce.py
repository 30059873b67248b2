"""The reduction from a profiler trace to the device's numbers: by hand
on a made-up trace, and on a slice of a trace recorded on the v5e
(``data/fleet-10k-wire-slice.xplane.pb``: four runs of the single step
of a fleet-10k wire run, cut from the full trace and written back
through the XSpace text format)."""

import os

import pytest

from benchmarks import trace_reduce as T

SLICE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "fleet-10k-wire-slice.xplane.pb")
PROGRAMS = {"packed_pipeline_step": 1, "chain": "ring_depth"}
MS = 1e6   # ns


def _device(chip, ops, modules=()):
    return (f"/device:TPU:{chip}", [
        (T.MODULES_LINE, list(modules)),
        (T.OPS_LINE, [(n, s * MS, d * MS) for n, s, d in ops])])


def _host(*events):
    return ("/host:CPU", [("python3", [(n, s * MS, d * MS)
                                       for n, s, d in events])])


def test_union_clip_and_gaps():
    busy = T.union([(0, 4), (2, 6), (10, 12), (11, 11.5), (20, 30)])
    assert busy == [(0, 6), (10, 12), (20, 30)]
    assert T.clip(busy, 5, 25) == [(5, 6), (10, 12), (20, 25)]
    assert T.gaps(T.clip(busy, 5, 25), 5, 25) == [(6, 10), (12, 20)]
    assert T.gaps([], 0, 3) == [(0, 3)]


def test_a_made_up_trace_reduces_as_by_hand():
    # window 0..100 ms.  chip 0 busy 10-30 (overlapping ops) and 50-60:
    # 30 ms; chip 1 busy 10-20: 10 ms.  A chain of K=4 ran 20 ms, two
    # single steps 12 and 8 ms: per step 5,5,5,5,12,8 -> median 5.
    planes = [
        _device(0, [("%a = f32[8]{0} fusion(x)", 10, 15),
                    ("%b = f32[8]{0} fusion(y)", 20, 10),
                    ("%a = f32[8]{0} fusion(x)", 50, 10)],
                modules=[("jit_chain(1)", 10 * MS, 20 * MS),
                         ("jit_packed_pipeline_step(2)", 50 * MS, 12 * MS),
                         ("jit_packed_pipeline_step(2)", 70 * MS, 8 * MS),
                         ("jit_other(3)", 200 * MS, 9 * MS)]),
        _device(1, [("%a = f32[8]{0} fusion(x)", 10, 10)]),
        _host((T.WINDOW_SPAN, 0, 100), ("bench.send", 0, 9),
              ("np.asarray(jax.Array)", 30, 20), ("bench.drain", 58, 30)),
    ]
    r = T.reduce(planes, PROGRAMS, ring_depth=4)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx((0.030 + 0.010) / 2)
    assert r["idle_share_worst"] == pytest.approx(0.90)      # chip 1
    assert r["device_step_ms"] == pytest.approx(5.0)
    assert r["program_runs"] == {"chain": 1, "packed_pipeline_step": 2}
    ops = dict(r["device_ops"])
    # seconds a chip, averaged over the two chips
    assert ops["%a f32[8]"] == pytest.approx((0.015 + 0.010 + 0.010) / 2)
    assert ops["%b f32[8]"] == pytest.approx(0.010 / 2)
    # chip 0 ran no program in 0-10 (send covers 9), 30-50 (the fetch),
    # 62-70 and 78-100 (the drain covers 8 and 10; the window's own span
    # names nothing); 60-62 and 70-78 are idle inside a program's run
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.send"] == pytest.approx(0.010)
    assert gaps["python3: np.asarray(jax.Array)"] == pytest.approx(0.020)
    assert gaps["bench.drain"] == pytest.approx(0.030)
    assert gaps[T.IN_PROGRAM] == pytest.approx(0.010)
    assert T.WINDOW_SPAN not in gaps
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.030)


def test_only_the_longest_gaps_are_named_one_by_one():
    ops = [("%a = f32[8]{0} fusion(x)", 2 * i, 1)
           for i in range(T.NAMED_GAPS + 50)]
    end = 2 * (T.NAMED_GAPS + 50)
    planes = [_device(0, ops),
              _host((T.WINDOW_SPAN, 0, end), ("bench.send", 0, end))]
    gaps = dict(T.reduce(planes, PROGRAMS, 1)["idle_gaps"])
    assert gaps["bench.send"] == pytest.approx(T.NAMED_GAPS * 1e-3)
    assert gaps[T.SHORTER] == pytest.approx(50e-3)


def test_no_device_operation_reduces_to_nothing():
    assert T.reduce([_host(("bench.send", 0, 5))], PROGRAMS, 8) == {}
    assert T.reduce_dir("/nonexistent", PROGRAMS, 8) == {}


def test_the_recorded_v5e_slice():
    planes = T.load(SLICE)
    names = [name for name, _ in planes]
    assert "/device:TPU:0" in names and "/host:CPU" in names
    r = T.reduce(planes, PROGRAMS, ring_depth=8)
    assert r["program_runs"] == {"packed_pipeline_step": 4}
    assert r["device_step_ms"] == pytest.approx(2.98334, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.011925797, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.123575762, rel=1e-6)
    assert r["idle_share_worst"] == pytest.approx(0.903494, rel=1e-5)
    assert r["device_ops"][0][0] == "%fusion.2 f32[16384,4]"
    assert len(r["device_ops"]) == T.TOP
    assert {"bench.connector", "bench.send"} <= {g[0] for g in
                                                 r["idle_gaps"]}
    # the gaps are the idle time, whoever they are named after
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)

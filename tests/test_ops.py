"""Kernel-level tests: point-in-polygon and time-ordered scatters."""

import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.ops.geo import points_in_polygons
from sitewhere_tpu.ops.scatter import (
    bincount_fixed,
    scatter_last_by_time,
    scatter_max_by_key,
)


from sitewhere_tpu.ops.geo import pad_polygon as pad_poly


def test_pip_triangle():
    tri = pad_poly([[0, 0], [4, 0], [2, 4]], 8)
    pts = jnp.array([[2.0, 1.0], [2.0, 5.0], [0.1, 3.0], [2.0, 3.9]], jnp.float32)
    out = np.asarray(points_in_polygons(pts, jnp.asarray(tri[None])))
    assert out[:, 0].tolist() == [True, False, False, True]


def test_pip_concave():
    # U-shaped (concave) polygon: notch between x=2..4 above y=2.
    poly = pad_poly(
        [[0, 0], [6, 0], [6, 5], [4, 5], [4, 2], [2, 2], [2, 5], [0, 5]], 16
    )
    pts = jnp.array(
        [[1.0, 4.0],   # left arm — inside
         [3.0, 4.0],   # in the notch — outside
         [5.0, 4.0],   # right arm — inside
         [3.0, 1.0]],  # base — inside
        jnp.float32,
    )
    out = np.asarray(points_in_polygons(pts, jnp.asarray(poly[None])))
    assert out[:, 0].tolist() == [True, False, True, True]


def test_pip_multiple_polygons():
    a = pad_poly([[0, 0], [1, 0], [1, 1], [0, 1]], 8)
    b = pad_poly([[10, 10], [12, 10], [12, 12], [10, 12]], 8)
    pts = jnp.array([[0.5, 0.5], [11.0, 11.0]], jnp.float32)
    out = np.asarray(points_in_polygons(pts, jnp.asarray(np.stack([a, b]))))
    assert out.tolist() == [[True, False], [False, True]]


def test_pip_degenerate_padding_zone():
    # All-zero (empty slot) polygon must contain nothing — including the
    # origin, where all padded vertices sit.
    zero = np.zeros((1, 8, 2), np.float32)
    pts = jnp.array([[0.0, 0.0], [1.0, 1.0]], jnp.float32)
    out = np.asarray(points_in_polygons(pts, jnp.asarray(zero)))
    assert not out.any()


def test_scatter_last_by_time_basic():
    cur_s = jnp.zeros(4, jnp.int32)
    cur_ns = jnp.zeros(4, jnp.int32)
    payload = jnp.zeros(4, jnp.float32)
    ids = jnp.array([1, 1, 2, 0], jnp.int32)
    ts_s = jnp.array([10, 20, 5, 7], jnp.int32)
    ts_ns = jnp.array([0, 0, 0, 0], jnp.int32)
    vals = jnp.array([1.0, 2.0, 3.0, 4.0], jnp.float32)
    mask = jnp.array([True, True, True, False])
    s, ns, (p,) = scatter_last_by_time(
        cur_s, cur_ns, (payload,), ids, ts_s, ts_ns, (vals,), mask
    )
    assert s.tolist() == [0, 20, 5, 0]
    assert p.tolist() == [0.0, 2.0, 3.0, 0.0]  # masked row 3 dropped


def test_scatter_last_by_time_stale_event_ignored():
    # Slot already at t=100; an event at t=50 must not regress it.
    cur_s = jnp.array([100], jnp.int32)
    cur_ns = jnp.array([7], jnp.int32)
    payload = jnp.array([9.0], jnp.float32)
    s, ns, (p,) = scatter_last_by_time(
        cur_s, cur_ns, (payload,),
        jnp.array([0]), jnp.array([50]), jnp.array([999]),
        (jnp.array([1.0]),), jnp.array([True]),
    )
    assert int(s[0]) == 100 and int(ns[0]) == 7 and float(p[0]) == 9.0


def test_scatter_last_by_time_ns_ordering():
    cur_s = jnp.array([100], jnp.int32)
    cur_ns = jnp.array([500], jnp.int32)
    payload = jnp.array([9.0], jnp.float32)
    # Same second, smaller ns -> ignored; larger ns -> wins.
    s, ns, (p,) = scatter_last_by_time(
        cur_s, cur_ns, (payload,),
        jnp.array([0, 0]), jnp.array([100, 100]), jnp.array([100, 600]),
        (jnp.array([1.0, 2.0]),), jnp.array([True, True]),
    )
    assert int(ns[0]) == 600 and float(p[0]) == 2.0


def test_scatter_out_of_range_ids_dropped():
    cur = jnp.zeros(2, jnp.int32)
    pay = jnp.zeros(2, jnp.float32)
    key, (p,) = scatter_max_by_key(
        cur, (pay,),
        jnp.array([-1, 7, 0]), jnp.array([5, 5, 5]),
        (jnp.array([1.0, 2.0, 3.0]),), jnp.array([True, True, True]),
    )
    assert key.tolist() == [5, 0]
    assert p.tolist() == [3.0, 0.0]


def test_bincount_fixed():
    out = bincount_fixed(
        jnp.array([0, 2, 2, 5, 1]), jnp.array([True, True, True, True, False]), 6
    )
    assert out.tolist() == [1, 0, 2, 0, 0, 1]


def test_bincount_negative_ids_dropped():
    out = bincount_fixed(jnp.array([-1, 0]), jnp.array([True, True]), 3)
    assert out.tolist() == [1, 0, 0]


def test_scatter_exact_tie_one_row_wins_all_columns():
    # Two events with IDENTICAL (s, ns): one whole row must win — columns
    # must never mix between tied rows.
    cur_s = jnp.zeros(2, jnp.int32)
    cur_ns = jnp.zeros(2, jnp.int32)
    lat = jnp.zeros(2, jnp.float32)
    lon = jnp.zeros(2, jnp.float32)
    s, ns, (la, lo) = scatter_last_by_time(
        cur_s, cur_ns, (lat, lon),
        jnp.array([1, 1]), jnp.array([1000, 1000]), jnp.array([0, 0]),
        (jnp.array([10.0, 20.0]), jnp.array([-10.0, -20.0])),
        jnp.array([True, True]),
    )
    # Highest row index wins: row 1 -> (20, -20).
    assert (float(la[1]), float(lo[1])) == (20.0, -20.0)


def test_pad_polygon_contract():
    p = pad_poly([[0, 0], [1, 0], [0, 1]], 6)
    assert p.shape == (6, 2)
    assert (p[3:] == p[2]).all()
    import pytest
    with pytest.raises(ValueError):
        pad_poly([[0, 0], [1, 0]], 6)  # too few verts
    with pytest.raises(ValueError):
        pad_poly([[0, 0]] * 9, 6)      # too many


def _winning_rows_loop(ids, keys, mask, cap):
    """Plain loop: per slot the masked row with the largest key tuple,
    the highest row on exact ties."""
    best = {}
    for r in range(len(ids)):
        if not mask[r] or not 0 <= ids[r] < cap:
            continue
        k = tuple(int(key[r]) for key in keys)
        if ids[r] not in best or k >= best[ids[r]][0]:
            best[ids[r]] = (k, r)
    won = np.zeros(len(ids), bool)
    won[[r for _, r in best.values()]] = True
    return won


@pytest.mark.parametrize("n_keys", [2, 1])
def test_winning_rows_match_the_plain_loop(n_keys):
    """The batch-sized winner election: same winners, same tie-breaks,
    same drops as a per-row loop — two-part time key and the single-key
    form (scatter_max_by_key's)."""
    from sitewhere_tpu.ops.scatter import winning_rows

    rng = np.random.default_rng(7)
    b, cap = 4096, 257
    ids = rng.integers(-3, cap + 3, b).astype(np.int32)
    keys = (rng.integers(100, 110, b).astype(np.int32),
            rng.integers(0, 4, b).astype(np.int32))[:n_keys]
    mask = rng.random(b) < 0.7
    got = winning_rows(jnp.asarray(ids), tuple(map(jnp.asarray, keys)),
                       jnp.asarray(mask), cap)
    assert got.shape == (b,)    # batch-sized: no [capacity] map
    assert np.asarray(got).tolist() == _winning_rows_loop(
        ids, keys, mask, cap).tolist()


@pytest.mark.parametrize("b,cap", [(512, 4096), (64, 64), (256, 7)])
def test_merge_rows_by_id_sums_a_batchs_changes_per_id(b, cap):
    """One merged row per id, at the id's last sorted row: the gathered
    row plus every change the batch states for the id (wrapping int32);
    ids out of range and the other rows of a run aim out of range."""
    from sitewhere_tpu.ops.scatter import merge_rows_by_id

    rng = np.random.default_rng(b)
    table = rng.integers(-2**31, 2**31, (cap, 8), dtype=np.int64).astype(np.int32)
    ids = rng.integers(-2, cap + 2, b).astype(np.int32)
    change = rng.integers(-2**31, 2**31, (b, 8), dtype=np.int64).astype(np.int32)
    base = table[np.clip(ids, 0, cap - 1)]
    targets, merged = merge_rows_by_id(
        jnp.asarray(ids), jnp.asarray(base), jnp.asarray(change), cap)
    targets, merged = np.asarray(targets), np.asarray(merged)
    want = table.astype(np.int64)
    for r in range(b):
        if 0 <= ids[r] < cap:
            want[ids[r]] += change[r]
    want = want.astype(np.int32)          # wraps as int32 adds do
    written = targets < cap
    assert sorted(targets[written]) == sorted(set(ids[(ids >= 0) & (ids < cap)]))
    assert len(set(targets.tolist())) == b     # unique, as the scatter promises
    got = table.copy()
    got[targets[written]] = merged[written]
    np.testing.assert_array_equal(got, want)

"""Masked, time-ordered scatters: the TPU replacement for per-event writes.

The reference's state materialization processes one Kafka record at a time
(``service-device-state/.../processing/DeviceStateProcessingLogic.java:46-80``),
so "last write wins" falls out of per-partition ordering.  In a batched SPMD
step many events for one device land in the same batch, so each slot needs
the row with the newest ``(ts_s, ts_ns)`` key, tie-broken by batch row index
(highest row wins) so exactly ONE event row writes all payload columns.

Everything here is sized by the BATCH, never by the table it updates:

1. :func:`winning_rows` — a stable multi-key sort groups the B rows by
   slot with the newest last; the run boundaries of the sorted rows ARE
   the winners, and they go back to batch order as a ``bool[B]`` through
   the sort's own permutation.  No ``[capacity]`` map is built.
2. the slots' current time keys are GATHERED at the batch's ids (B rows),
   :func:`newer_or_equal` makes the newest-wins comparison on B rows,
3. the rows that win are SCATTERED back with ``unique_indices=True,
   mode="drop"``: at most one row per slot survives step 1, and losers
   and masked rows are aimed at distinct out-of-range targets
   (:func:`drop_targets`).  Unique indices keep the scatter off XLA's
   serialized duplicate-index update loop on the TPU — the reason the
   winners are found by sorting in the first place.

Where several batch rows change different columns of one slot's row,
:func:`merge_rows_by_id` merges them first (a sort and a running sum, on
batch-sized arrays), so that one whole row per slot is scattered.

One algorithm on every backend and for every table shape: its cost
follows the batch it is given.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def winning_rows(
    ids: jax.Array,
    keys: Sequence[jax.Array],
    mask: jax.Array,
    capacity: int,
) -> jax.Array:
    """``bool[B]``: True at the ONE batch row that wins its slot — the
    largest lexicographic ``keys`` tuple among the masked rows targeting
    the slot, the highest row on exact ties.  Rows with ``mask=False``
    or ids outside ``[0, capacity)`` never win.

    The stable ascending sort on ``(id, *keys)`` leaves each slot's
    winning row LAST in its run (stability preserves batch order among
    equal keys, giving the highest-row tie-break); the sorted row
    indices are a permutation of ``arange(B)``, so the boundary flags
    scatter back to batch order with unique indices.
    """
    b = ids.shape[0]
    mask = mask & (ids >= 0) & (ids < capacity)
    eff = jnp.where(mask, ids, capacity).astype(jnp.int32)
    rows = jnp.arange(b, dtype=jnp.int32)
    sorted_ops = lax.sort(
        (eff, *keys, rows), num_keys=1 + len(keys), is_stable=True
    )
    eff_s, rows_s = sorted_ops[0], sorted_ops[-1]
    nxt = jnp.concatenate([eff_s[1:], jnp.full((1,), capacity + 1, jnp.int32)])
    boundary = (eff_s != nxt) & (eff_s < capacity)
    return jnp.zeros((b,), bool).at[rows_s].set(boundary, unique_indices=True)


def newer_or_equal(
    ts_s: jax.Array, ts_ns: jax.Array, cur_s: jax.Array, cur_ns: jax.Array
) -> jax.Array:
    """The newest-wins comparison: an event at least as new as the slot's
    current ``(ts_s, ts_ns)`` key replaces it (events win exact ties, the
    same contract per-partition ordering gives the reference)."""
    return (ts_s > cur_s) | ((ts_s == cur_s) & (ts_ns >= cur_ns))


def drop_targets(ids: jax.Array, write: jax.Array, capacity: int) -> jax.Array:
    """Scatter targets for a ``unique_indices=True, mode="drop"`` scatter:
    ``ids`` where ``write``, else an out-of-range index of the row's own
    (``capacity + row``), so the index vector is unique as promised even
    among the rows that are dropped."""
    rows = jnp.arange(ids.shape[0], dtype=jnp.int32)
    return jnp.where(write, ids, capacity + rows).astype(jnp.int32)


def merge_rows_by_id(
    ids: jax.Array, base: jax.Array, change: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Sum the batch rows' ``change [B, W]`` (wrapping int32) per id and
    add each id's total to its ``base [B, W]`` row, all on batch-sized
    arrays: a sort by id brings an id's rows together, the running sum
    down the sorted rows less its value at the previous id's last row is
    the id's total, and the LAST row of each run carries ``base +
    total``.  ``base`` must hold the same row wherever ``ids`` agree
    (rows gathered at ``ids`` do).

    Returns ``(targets int32[B], merged int32[B, W])`` in sorted order:
    ``targets`` is the id at each run's last row and a distinct
    out-of-range index elsewhere (rows with ids outside ``[0, capacity)``
    included), ready for ONE ``unique_indices=True, mode="drop"``
    scatter of whole rows.
    """
    b = ids.shape[0]
    pos = jnp.arange(b, dtype=jnp.int32)
    in_range = (ids >= 0) & (ids < capacity)
    ids_s, order = lax.sort(
        (jnp.where(in_range, ids, capacity).astype(jnp.int32), pos),
        num_keys=1, is_stable=True)
    nxt = jnp.concatenate([ids_s[1:], jnp.full((1,), capacity + 1, jnp.int32)])
    last = (ids_s != nxt) & (ids_s < capacity)
    running = jnp.cumsum(change[order], axis=0, dtype=jnp.int32)
    prev_last = jnp.concatenate([
        jnp.full((1,), -1, jnp.int32),
        lax.cummax(jnp.where(last, pos, -1))[:-1]])
    before = jnp.where((prev_last >= 0)[:, None],
                       running[jnp.maximum(prev_last, 0)], 0)
    return (jnp.where(last, ids_s, capacity + pos),
            base[order] + (running - before))


def set_rows(col: jax.Array, targets, vals) -> jax.Array:
    """``col`` with ``vals`` written at :func:`drop_targets` (an index
    vector, or a tuple of them for a ``[D, M]`` column): the one
    unique-index, out-of-range-dropping scatter everything here ends in."""
    return col.at[targets].set(
        jnp.asarray(vals, col.dtype), mode="drop", unique_indices=True)


def scatter_last_by_time(
    cur_ts_s: jax.Array,
    cur_ts_ns: jax.Array,
    cur_payload: Sequence[jax.Array],
    ids: jax.Array,
    ts_s: jax.Array,
    ts_ns: jax.Array,
    payload: Sequence[jax.Array],
    mask: jax.Array,
) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, ...]]:
    """Scatter ``payload`` rows into per-id slots, newest ``(ts_s, ts_ns)`` wins.

    Args:
      cur_ts_s/cur_ts_ns: ``int32[D]`` current per-slot time key.
      cur_payload: arrays of shape ``[D, ...]`` to update alongside the key.
      ids: ``int32[B]`` target slot per event (rows with ``mask=False`` or
        out-of-range ids are dropped).
      ts_s/ts_ns: ``int32[B]`` event time key.
      payload: arrays of shape ``[B, ...]`` matching ``cur_payload``.
      mask: ``bool[B]``.

    Returns:
      ``(new_ts_s, new_ts_ns, new_payload)``.
    """
    if len(cur_payload) != len(payload):
        raise ValueError(
            f"payload arity mismatch: {len(cur_payload)} state arrays vs "
            f"{len(payload)} event arrays (pass tuples, not bare arrays)"
        )
    capacity = cur_ts_s.shape[0]
    safe = jnp.clip(ids, 0, capacity - 1)
    write = winning_rows(ids, (ts_s, ts_ns), mask, capacity) & newer_or_equal(
        ts_s, ts_ns, cur_ts_s[safe], cur_ts_ns[safe])
    tgt = drop_targets(ids, write, capacity)
    return (
        set_rows(cur_ts_s, tgt, ts_s),
        set_rows(cur_ts_ns, tgt, ts_ns),
        tuple(set_rows(cur, tgt, val)
              for cur, val in zip(cur_payload, payload)),
    )


def scatter_max_by_key(
    cur_key: jax.Array,
    cur_payload: Sequence[jax.Array],
    ids: jax.Array,
    key: jax.Array,
    payload: Sequence[jax.Array],
    mask: jax.Array,
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Single-key (seconds-only) variant of :func:`scatter_last_by_time`."""
    if len(cur_payload) != len(payload):
        raise ValueError(
            f"payload arity mismatch: {len(cur_payload)} state arrays vs "
            f"{len(payload)} event arrays (pass tuples, not bare arrays)"
        )
    capacity = cur_key.shape[0]
    safe = jnp.clip(ids, 0, capacity - 1)
    write = winning_rows(ids, (key,), mask, capacity) & (key >= cur_key[safe])
    tgt = drop_targets(ids, write, capacity)
    return set_rows(cur_key, tgt, key), tuple(
        set_rows(cur, tgt, val) for cur, val in zip(cur_payload, payload))


def bincount_fixed(ids: jax.Array, mask: jax.Array, length: int) -> jax.Array:
    """Masked bincount with static length (metrics rollups).

    One-hot compare + column sum: for small ``length`` this is a [B, length]
    reduction XLA fuses, avoiding the duplicate-index scatter-add path.
    """
    hit = (ids[:, None] == jnp.arange(length, dtype=ids.dtype)[None, :]) & (
        mask[:, None]
    )
    return hit.sum(axis=0, dtype=jnp.int32)

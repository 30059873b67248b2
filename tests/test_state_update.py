"""The state update reads and writes only the rows a batch names.

Semantics: against a plain numpy loop (one event at a time, as the
reference's per-record merge does it), with the registry much larger than
the batch and with the batch as large as the registry; then the same
through every packed path against the unpacked ``pipeline_step``.

Structure: the compiled packed step holds nothing sized by the registry
but the carry (written in place), the registry table it reads, and the
``present_now`` vector — at two capacities, on the CPU here and for the
chip in ``tests/test_aot_check.py``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.pipeline import pipeline_step, update_device_state
from sitewhere_tpu.pipeline.packed import (
    build_packed_chain,
    build_packed_step,
    pack_batch_host,
    pack_state,
    pack_tables,
    unpack_batch,
    unpack_state,
)
from sitewhere_tpu.pipeline.step import default_ewma_taus, fold_ewma_arrays
from sitewhere_tpu.schema import (
    AssignmentStatus,
    DeviceState,
    EventType,
    Registry,
    RuleTable,
    ZoneTable,
    as_numpy,
)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

STATE_FIELDS = tuple(DeviceState.__dataclass_fields__)
SHAPES = {"registry_much_larger": (4096, 8, 64),   # D >> B
          "batch_as_large": (64, 8, 64)}           # B = D
SCENARIOS = ("random", "one_device", "exact_ties", "older_than_slot",
             "slot_collisions")


def _seeded_state(cap, M, K=3, seed=1):
    """A state whose slots already hold events around ts 1,500, so a batch
    stamped 1,000..2,000 is older than some slots and newer than others."""
    rng = np.random.default_rng(seed)

    def ints(shape, lo, hi):
        return jnp.asarray(rng.integers(lo, hi, shape), jnp.int32)

    def floats(shape):
        return jnp.asarray(rng.uniform(0, 50, shape), jnp.float32)

    return DeviceState.empty(cap, M, K).replace(
        last_event_ts_s=ints(cap, 1_400, 1_600),
        last_event_ts_ns=ints(cap, 0, 4),
        last_event_type=ints(cap, 0, 4),
        last_location_ts_s=ints(cap, 1_400, 1_600),
        last_location_ts_ns=ints(cap, 0, 4),
        last_lat=floats(cap), last_lon=floats(cap),
        last_alert_ts_s=ints(cap, 1_400, 1_600),
        last_alert_code=ints(cap, 0, 9),
        last_values=floats((cap, M)),
        last_value_ts_s=ints((cap, M), 0, 1_600),   # 0: never seeded
        last_value_ts_ns=ints((cap, M), 0, 4),
        ewma_values=floats((cap, M, K)),
        presence_missing=jnp.asarray(rng.random(cap) < 0.3),
        nonfinite_count=ints(cap, 0, 3),
    )


def _columns(scenario, cap, M, width, seed):
    """Batch columns: duplicates of a device, out-of-range and negative
    ids, invalid rows, ``update_state`` false, NULL and colliding
    measurement types, all four families — more of one per scenario."""
    rng = np.random.default_rng(seed)
    hot = min(cap, 24)   # few devices: every batch holds duplicates
    device_id = rng.integers(0, hot, width).astype(np.int32)
    device_id[rng.random(width) < 0.08] = -3
    device_id[rng.random(width) < 0.08] = cap + 5
    ts_s = rng.integers(1_000, 2_000, width).astype(np.int32)
    ts_ns = rng.integers(0, 4, width).astype(np.int32)
    mtype = rng.integers(-1, M, width).astype(np.int32)
    if scenario == "one_device":
        device_id[:] = 7
    if scenario == "exact_ties":         # one stamp: highest row wins all
        ts_s[:] = 1_700
        ts_ns[:] = 2
    if scenario == "older_than_slot":    # every event older than the state
        ts_s[:] = rng.integers(100, 1_000, width)
    if scenario == "slot_collisions":    # types collide mod M
        mtype = rng.integers(0, 4 * M, width).astype(np.int32)
    return dict(
        valid=rng.random(width) < 0.9,
        device_id=device_id,
        tenant_id=np.zeros(width, np.int32),
        event_type=rng.integers(0, 4, width).astype(np.int32),
        ts_s=ts_s, ts_ns=ts_ns, mtype_id=mtype,
        value=rng.uniform(0, 100, width).astype(np.float32),
        lat=rng.uniform(-20, 20, width).astype(np.float32),
        lon=rng.uniform(-20, 20, width).astype(np.float32),
        elevation=rng.uniform(0, 9, width).astype(np.float32),
        alert_code=rng.integers(0, 9, width).astype(np.int32),
        alert_level=rng.integers(0, 3, width).astype(np.int32),
        command_id=np.full(width, NULL_ID, np.int32),
        payload_ref=np.arange(width, dtype=np.int32),
        update_state=rng.random(width) < 0.9,
    )


def _event_batch(cols):
    width = len(cols["valid"])
    return unpack_batch(*map(jnp.asarray, pack_batch_host(cols, width)))


def _loop_update(state, cols, accepted, ewma_candidates):
    """The merge as a plain loop over the batch's rows, one event at a
    time: an event at least as new as what its slot holds replaces it."""
    s = {f: np.array(getattr(state, f)) for f in STATE_FIELDS}
    D, M = s["last_values"].shape
    present = np.zeros(D, bool)

    def newer(r, cur_s, cur_ns):
        return (cols["ts_s"][r], cols["ts_ns"][r]) >= (cur_s, cur_ns)

    for r in range(len(accepted)):
        d = int(cols["device_id"][r])
        if not (accepted[r] and cols["update_state"][r] and 0 <= d < D):
            continue
        present[d] = True
        s["presence_missing"][d] = False
        stamp = (cols["ts_s"][r], cols["ts_ns"][r])
        if newer(r, s["last_event_ts_s"][d], s["last_event_ts_ns"][d]):
            s["last_event_ts_s"][d], s["last_event_ts_ns"][d] = stamp
            s["last_event_type"][d] = cols["event_type"][r]
        kind = cols["event_type"][r]
        if kind == EventType.LOCATION and newer(
                r, s["last_location_ts_s"][d], s["last_location_ts_ns"][d]):
            s["last_location_ts_s"][d], s["last_location_ts_ns"][d] = stamp
            s["last_lat"][d] = cols["lat"][r]
            s["last_lon"][d] = cols["lon"][r]
            s["last_elevation"][d] = cols["elevation"][r]
        if kind == EventType.ALERT and newer(
                r, s["last_alert_ts_s"][d], s["last_alert_ts_ns"][d]):
            s["last_alert_ts_s"][d], s["last_alert_ts_ns"][d] = stamp
            s["last_alert_code"][d] = cols["alert_code"][r]
        if kind == EventType.MEASUREMENT and cols["mtype_id"][r] >= 0:
            m = int(cols["mtype_id"][r]) % M
            if newer(r, s["last_value_ts_s"][d, m],
                     s["last_value_ts_ns"][d, m]):
                s["last_value_ts_s"][d, m], s["last_value_ts_ns"][d, m] = stamp
                s["last_values"][d, m] = cols["value"][r]
                s["ewma_values"][d, m] = ewma_candidates[r]
    return s, present


def _candidates(state, cols):
    """EWMA candidates folded against the PRE-batch slot, per row."""
    D, M = state.last_values.shape
    ids = np.clip(cols["device_id"], 0, D - 1)
    slot = np.where(cols["mtype_id"] >= 0, cols["mtype_id"] % M, 0)
    return np.asarray(fold_ewma_arrays(
        state.last_value_ts_s[ids, slot], state.last_value_ts_ns[ids, slot],
        state.ewma_values[ids, slot], jnp.asarray(cols["ts_s"]),
        jnp.asarray(cols["ts_ns"]), jnp.asarray(cols["value"]),
        default_ewma_taus(state.num_ewma_scales)))


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("shape", SHAPES)
def test_update_matches_the_plain_loop(shape, scenario):
    cap, M, width = SHAPES[shape]
    state = _seeded_state(cap, M)
    cols = _columns(scenario, cap, M, width, seed=len(scenario))
    accepted = np.random.default_rng(3).random(width) < 0.85
    candidates = _candidates(state, cols)
    new_state, present_now = jax.jit(update_device_state)(
        state, _event_batch(cols), jnp.asarray(accepted),
        jnp.asarray(candidates))
    want, want_present = _loop_update(state, cols, accepted, candidates)
    assert present_now.shape == (cap,)
    np.testing.assert_array_equal(np.asarray(present_now), want_present)
    for f in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(new_state, f)), want[f], err_msg=f)
    # the scenario did exercise what it names
    if scenario != "older_than_slot":
        assert want_present.any()
    else:
        assert np.array_equal(want["last_event_ts_s"],
                              np.asarray(state.last_event_ts_s))


# -- the packed paths against the unpacked pipeline_step --------------------


def _tables(cap, n_active):
    idx = jnp.arange(cap)
    on = idx < n_active
    registry = Registry.empty(cap).replace(
        active=on, tenant_id=jnp.where(on, 0, -1),
        assignment_status=jnp.where(
            idx < n_active - 2, AssignmentStatus.ACTIVE, 0),
        assignment_id=jnp.where(on, idx, -1),
        area_id=jnp.where(on, idx % 5, -1))
    rules = RuleTable.empty(8)
    rules = rules.replace(
        active=rules.active.at[0].set(True),
        threshold=rules.threshold.at[0].set(50.0),
        alert_code=rules.alert_code.at[0].set(7))
    return registry, rules, ZoneTable.empty(4, max_verts=8)


def _poisoned(cols, seed):
    """A few NaN/Inf rows, so the nonfinite count is merged too — twice
    for one device in one batch."""
    rng = np.random.default_rng(seed)
    cols = dict(cols)
    bad = rng.choice(len(cols["value"]), 5, replace=False)
    cols["value"] = cols["value"].copy()
    cols["value"][bad] = np.nan
    cols["device_id"] = cols["device_id"].copy()
    cols["device_id"][bad[:2]] = 5
    return cols


def _unpacked_run(cap, M, batches):
    registry, rules, zones = _tables(cap, cap)
    state = _seeded_state(cap, M)
    step = jax.jit(pipeline_step)
    outs = []
    for cols in batches:
        state, out = step(registry, state, rules, zones, _event_batch(cols))
        outs.append(as_numpy(out))
    return state, outs


def _assert_same_state(got: DeviceState, want: DeviceState):
    for f in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f)


def _run_packed_step(cap, M, batches, mesh, donate=False):
    registry, rules, zones = _tables(cap, cap)
    tables = pack_tables(registry, rules, zones)
    ps = pack_state(_seeded_state(cap, M))
    step = build_packed_step(donate)
    presents = []
    for cols in batches:
        bi, bf = pack_batch_host(cols, len(cols["valid"]))
        ps, _oi, _met, present = step(tables, ps, bi, bf)
        presents.append(np.asarray(present))
    return unpack_state(ps), presents


def _run_packed_chain(cap, M, batches, mesh, donate=False):
    registry, rules, zones = _tables(cap, cap)
    tables = pack_tables(registry, rules, zones)
    ps = pack_state(_seeded_state(cap, M))
    packed = [pack_batch_host(c, len(c["valid"])) for c in batches]
    chain = build_packed_chain(len(batches), donate=donate)
    ps, _ois, _mets, present = chain(
        tables, ps, *[b[0] for b in packed], *[b[1] for b in packed])
    return unpack_state(ps), np.asarray(present)


def _route(cols, cap, n_shards):
    """The rows in a batch ``n_shards`` times as wide, each in the
    segment of the shard owning its device (the host batcher's job; rows
    no shard owns go to shard 0's), the rest of every segment invalid."""
    seg, per = len(cols["valid"]), cap // n_shards
    owner = np.where((cols["device_id"] >= 0) & (cols["device_id"] < cap),
                     cols["device_id"] // per, 0)
    out = {f: np.zeros(seg * n_shards, c.dtype) for f, c in cols.items()}
    out["device_id"][:] = NULL_ID
    for s in range(n_shards):
        rows = np.nonzero(owner == s)[0]
        for f, c in cols.items():
            out[f][s * seg:s * seg + len(rows)] = c[rows]
    return out


def _run_sharded_step(cap, M, batches, mesh, donate=False):
    from sitewhere_tpu.pipeline.sharded import (
        build_sharded_packed_step,
        place_packed_batch,
        place_packed_state,
        place_packed_tables,
    )

    registry, rules, zones = _tables(cap, cap)
    tables = place_packed_tables(mesh, pack_tables(registry, rules, zones))
    ps = place_packed_state(mesh, pack_state(_seeded_state(cap, M)))
    step = build_sharded_packed_step(mesh, donate=donate)
    presents = []
    for cols in batches:
        bi, bf = pack_batch_host(cols, len(cols["valid"]))
        ps, _oi, _met, present = step(
            tables, ps, *place_packed_batch(mesh, bi, bf))
        presents.append(np.asarray(present))
    assert len(jax.tree.leaves(ps)[0].sharding.device_set) == 4
    return unpack_state(ps), presents


@pytest.fixture(scope="module")
def mesh4():
    from sitewhere_tpu.parallel.mesh import make_mesh

    return make_mesh(n_devices=4)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("path", ["packed_step", "packed_step_donated",
                                  "packed_chain_k8", "sharded_packed_step_4",
                                  "sharded_packed_step_4_donated"])
def test_packed_paths_match_the_unpacked_step(path, shape, mesh4):
    cap, M, width = SHAPES[shape]
    batches = []
    for i in range(8):
        cols = _poisoned(_columns(SCENARIOS[i % len(SCENARIOS)], cap, M,
                                  width, seed=10 + i), seed=i)
        if cap >= 4096:    # devices on every shard's block of the registry
            cols["device_id"] = np.where(
                (cols["device_id"] >= 0) & (cols["device_id"] < cap),
                (cols["device_id"] * 1021) % cap, cols["device_id"]
            ).astype(np.int32)
        batches.append(_route(cols, cap, 4))   # every path: the same rows
    want_state, want_outs = _unpacked_run(cap, M, batches)
    run = {"packed_step": _run_packed_step,
           "packed_chain_k8": _run_packed_chain,
           "sharded_packed_step_4": _run_sharded_step}[
               path.removesuffix("_donated")]
    got_state, present = run(cap, M, batches, mesh4,
                             donate=path.endswith("_donated"))
    _assert_same_state(got_state, want_state)
    assert int(np.asarray(want_state.nonfinite_count).sum()) > int(
        np.asarray(_seeded_state(cap, M).nonfinite_count).sum())
    if path == "packed_chain_k8":   # the chain ORs its steps' presence
        np.testing.assert_array_equal(
            present, np.any([o.present_now for o in want_outs], axis=0))
    else:
        for got, out in zip(present, want_outs):
            np.testing.assert_array_equal(got, out.present_now)


# -- structure ---------------------------------------------------------------


@pytest.mark.parametrize("program", ["packed_step", "packed_chain_k8_donated"])
def test_nothing_in_the_step_is_sized_by_the_registry(program):
    """Lowered at one width and two capacities, compiled for the backend
    here: every value with at least ``capacity`` elements is the carry
    (updated in place and never copied: both programs donate it, PR 36),
    the registry table, or a ``[capacity]`` vector."""
    import aot_check

    for capacity in (1 << 14, 1 << 18):
        hlo = aot_check.compiled_hlo(program, capacity, width=32)
        aot_check.assert_step_costs_the_batch(hlo, capacity, donated=True)


def test_the_guard_sees_a_registry_sized_intermediate():
    """The guard fails on the old shape of the update: a ``[capacity x
    M, k]`` pack built before B rows are gathered from it."""
    import aot_check

    cap, M = 1 << 14, 8

    def grows(ps, ids):
        pack = jnp.stack([ps.rows[:, 16:16 + M].reshape(-1)] * 2, axis=1)
        return ps.replace(rows=ps.rows.at[ids, 0].set(pack[ids * M, 0]))

    ps = jax.eval_shape(lambda: pack_state(DeviceState.empty(cap, M)))
    hlo = jax.jit(grows).lower(
        ps, jax.ShapeDtypeStruct((64,), jnp.int32)).compile().as_text()
    with pytest.raises(AssertionError, match="sized by the registry"):
        aot_check.assert_step_costs_the_batch(hlo, cap, donated=False)

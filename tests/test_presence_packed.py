"""The served presence sweep runs on the packed carry.

``DeviceStateManager.apply_presence_sweep`` where the manager holds the
packed epoch: equal to ``presence_sweep`` on the unpacked twin (flags,
send-once, re-arm), the epoch stays packed between two steps around a
sweep, a report of any length compiles nothing, and a sweep that lands
while a chain holds the lease is merged at the chain's commit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.ids import IdentityMap, NULL_ID
from sitewhere_tpu.pipeline.packed import (
    BATCH_F,
    BATCH_I,
    pack_batch_host,
    pack_state,
    pack_tables,
    packed_pipeline_step,
    unpack_state,
)
from sitewhere_tpu.schema import (
    DeviceState,
    EventType,
    RuleTable,
    ZoneTable,
    as_numpy,
)
from sitewhere_tpu.state import DeviceStateManager, presence_sweep
from sitewhere_tpu.state import manager as manager_module

from helpers import make_batch, make_registry, measurement

CAP = 64
N_DEV = 32

_COMPILES = {"n": 0}


def _count_compiles(event: str, seconds: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["n"] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


@pytest.fixture
def manager():
    im = IdentityMap(capacity=CAP)
    for i in range(N_DEV):
        assert im.device.mint(f"dev-{i}") == i
    return DeviceStateManager(CAP, im)


@pytest.fixture
def codec_calls(monkeypatch):
    """Counts every pack/unpack the manager makes."""
    calls = {"pack": 0, "unpack": 0}
    pack, unpack = manager_module._packed_codecs()

    def counting():
        def counted_pack(state):
            calls["pack"] += 1
            return pack(state)

        def counted_unpack(packed):
            calls["unpack"] += 1
            return unpack(packed)
        return counted_pack, counted_unpack
    monkeypatch.setattr(manager_module, "_packed_codecs", counting)
    return calls


_STEP = jax.jit(packed_pipeline_step)


def packed_step(manager, rows, ps=None, commit=True):
    """One packed single step as the dispatcher makes it: read the
    packed epoch, step, commit against the epoch read."""
    registry = make_registry(capacity=CAP, n_devices=N_DEV)
    tables = pack_tables(registry, RuleTable.empty(4), ZoneTable.empty(4))
    host = as_numpy(make_batch(rows))
    cols = {f: np.asarray(getattr(host, f)) for f in BATCH_I + BATCH_F}
    bi, bf = pack_batch_host(cols, len(rows))
    epoch = manager.current_packed if ps is None else ps
    new_ps, _oi, _mets, present = _STEP(tables, epoch, bi, bf)
    if commit:
        manager.commit_packed(new_ps, present_now=present, read_epoch=epoch)
    return new_ps, present


def random_state(seed: int) -> DeviceState:
    """Devices never heard, heard long ago, heard lately, some flagged."""
    rng = np.random.default_rng(seed)
    state = DeviceState.empty(CAP)
    heard = rng.random(CAP) < 0.8
    ts = rng.integers(1_000, 100_000, CAP).astype(np.int32)
    return state.replace(
        last_event_type=jnp.asarray(np.where(
            heard, int(EventType.MEASUREMENT), NULL_ID).astype(np.int32)),
        last_event_ts_s=jnp.asarray(np.where(heard, ts, 0).astype(np.int32)),
        presence_missing=jnp.asarray(heard & (rng.random(CAP) < 0.2)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_packed_sweep_equals_the_unpacked_sweep(manager, seed):
    state = random_state(seed)
    manager._state, manager._packed = None, jax.jit(pack_state)(state)
    now, after = 90_000, 30_000
    want_state, want_newly = presence_sweep(
        state, jnp.int32(now), jnp.int32(after))
    report = manager.apply_presence_sweep(now, after)
    want_ids = np.nonzero(np.asarray(want_newly))[0]
    assert want_ids.size > 0
    assert report["device_id"].tolist() == want_ids.tolist()
    assert (report["event_type"] == int(EventType.STATE_CHANGE)).all()
    assert (report["ts_s"] == now).all() and not report["update_state"].any()
    assert manager._packed is not None and manager._state is None
    got = jax.jit(unpack_state)(manager._packed)
    for field in DeviceState.__dataclass_fields__:
        assert np.array_equal(np.asarray(getattr(got, field)),
                              np.asarray(getattr(want_state, field))), field
    # send-once: the flags it set are its own memory
    assert manager.apply_presence_sweep(now + 1, after) is None
    # any accepted event re-arms: the device can be reported again
    dev = int(want_ids[want_ids < N_DEV][0])
    packed_step(manager, [measurement(dev, ts=now + 2)])
    assert dev not in manager.missing_device_ids()
    again = manager.apply_presence_sweep(now + 2 + after + 1, after)
    assert dev in again["device_id"].tolist()


def test_the_epoch_stays_packed_between_two_steps_around_a_sweep(
        manager, codec_calls):
    packed_step(manager, [measurement(0, ts=1_000), measurement(1, ts=50_000)])
    assert codec_calls == {"pack": 1, "unpack": 0}    # the first epoch
    before = manager.current_packed
    report = manager.apply_presence_sweep(60_000, 30_000)
    assert report["device_id"].tolist() == [0]
    swept = manager._packed
    assert swept is not None and swept is not before
    assert manager._state is None          # no unpacked twin was built
    packed_step(manager, [measurement(1, ts=60_001)])
    assert codec_calls == {"pack": 1, "unpack": 0}
    assert manager.missing_device_ids() == [0]


def test_a_step_the_sweep_overtook_merges_its_flags_without_packing(
        manager, codec_calls):
    """The step read the epoch, the sweep replaced it, the step commits:
    the sweep's flag survives for the device the step did not merge."""
    packed_step(manager, [measurement(0, ts=1_000), measurement(5, ts=1_000)])
    epoch = manager.current_packed
    new_ps, present = packed_step(manager, [measurement(0, ts=90_000)],
                                  ps=epoch, commit=False)
    assert sorted(manager.apply_presence_sweep(80_000, 10_000)
                  ["device_id"].tolist()) == [0, 5]
    manager.commit_packed(new_ps, present_now=present, read_epoch=epoch)
    assert manager.missing_device_ids() == [5]
    assert codec_calls["pack"] == 1


def test_a_report_of_any_length_compiles_nothing(manager):
    packed_step(manager, [measurement(i, ts=1_000 + i) for i in range(16)])
    manager.warm_presence_programs()
    assert manager.apply_presence_sweep(900, 50) is None   # nobody overdue
    seen = _COMPILES["n"]
    lengths = []
    # devices 0, then 1-3, then 4-10 cross one after another
    for now in (1_051, 1_054, 1_061):
        report = manager.apply_presence_sweep(now, 50)
        lengths.append(len(report["device_id"]))
        assert all(type(col) is np.ndarray for col in report.values())
    assert lengths == [1, 3, 7]
    assert _COMPILES["n"] == seen


def test_a_sweep_against_a_leased_chain_in_flight(manager, codec_calls):
    """The chain holds the packed lease, so the sweep takes the twin the
    lease built; the chain's commit packs the swept twin once and merges
    its flags (the one merge ``commit_packed`` has)."""
    packed_step(manager, [measurement(0, ts=1_000), measurement(5, ts=1_000)])
    ps, token = manager.lease_packed()
    assert codec_calls == {"pack": 1, "unpack": 1}     # the lease's twin
    new_ps, present = packed_step(manager, [measurement(0, ts=90_000)],
                                  ps=ps, commit=False)
    report = manager.apply_presence_sweep(80_000, 10_000)
    assert sorted(report["device_id"].tolist()) == [0, 5]
    assert manager._packed is None         # still leased: the twin was swept
    manager.commit_packed(new_ps, present_now=present, lease_token=token)
    assert codec_calls == {"pack": 2, "unpack": 1}
    # dev-0 (merged by the chain, a fresh event) cleared; dev-5 flagged
    assert manager.missing_device_ids() == [5]
    assert manager.apply_presence_sweep(80_001, 10_000) is None


def test_the_sweeps_instruments(manager):
    from sitewhere_tpu.runtime.metrics import MetricsRegistry

    metrics = MetricsRegistry()
    manager.bind_metrics(metrics)
    snap = metrics.snapshot()
    assert snap["counters"]["presence.sweeps"] == 0       # registered idle
    assert snap["timers"]["presence.sweep_s"]["count"] == 0
    packed_step(manager, [measurement(0, ts=1_000), measurement(1, ts=1_000)])
    manager.apply_presence_sweep(50_000, 30_000)
    manager.apply_presence_sweep(50_001, 30_000)
    snap = metrics.snapshot()
    assert snap["counters"]["presence.sweeps"] == 2
    assert snap["counters"]["presence.reported"] == 2
    assert snap["timers"]["presence.sweep_s"]["count"] == 2
    # the sweep program's own time is the device trace's, not a host span
    assert "presence.sweep_device_s" not in snap["timers"]

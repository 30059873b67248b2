"""The single step donates the carry it replaces (PR 36).

On the chip the dispatcher builds the single packed step with the carry
donated and hands it the state manager's epoch under the manager's lock
(``DeviceStateManager.step_packed``): read, dispatch and commit with
nothing in between, so the scatter writes in place instead of into a
copy of the whole carry.  The CPU backend honours donation too (a
donated input reads ``is_deleted()``), so these tests force the chip's
wiring on a small live instance and hold it to four things: the epoch
it consumed is gone and the rows are the copying step's, byte for byte;
readers and sweeps racing the steps never touch a deleted buffer nor
lose a flag; a device fault and the bisect leave the epoch whole; the
boot warm-up leaves it as it was.
"""

import json
import threading

import numpy as np
import pytest

from sitewhere_tpu.runtime import faults

TS0 = 1_754_600_000          # old events: the sweep finds them silent
NOW = TS0 + 100_000          # the sweeps' clock; fresh events stamp it
MISSING_AFTER = 1800


@pytest.fixture(autouse=True)
def _clean_device_faults():
    faults.device_clear()
    yield
    faults.device_clear()


@pytest.fixture
def make_inst(tmp_path):
    """``make_inst(donate)``: a started instance with four devices whose
    single step donates its carry (the chip's wiring) or copies it (the
    CPU's own)."""
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.pipeline.packed import build_packed_step
    from sitewhere_tpu.runtime.config import Config

    made = []

    def make(donate: bool):
        inst = Instance(Config({
            "instance": {"id": f"donate-{len(made)}",
                         "data_dir": str(tmp_path / f"data{len(made)}")},
            "pipeline": {"width": 64, "registry_capacity": 128,
                         "mtype_slots": 4, "deadline_ms": 5.0,
                         "n_shards": 1},
            "overload": {"cooldown_s": 3600.0},
            "presence": {"scan_interval_s": 3600.0,
                         "missing_after_s": MISSING_AFTER},
        }, apply_env=False))
        made.append(inst)
        if donate:
            inst.dispatcher._ring_donate = True
            inst.dispatcher._packed_step = build_packed_step(donate=True)
        inst.start()
        inst.device_management.create_device_type(token="sensor", name="S")
        for i in range(4):
            inst.device_management.create_device(token=f"d-{i}",
                                                 device_type="sensor")
            inst.device_management.create_device_assignment(
                device=f"d-{i}")
        return inst

    yield make
    for inst in made:
        inst.stop()
        inst.terminate()


def _lines(values, token, ts0):
    return "\n".join(json.dumps({
        "deviceToken": token, "type": "Measurement",
        "request": {"name": "temp", "value": v, "eventDate": ts0 + i},
    }) for i, v in enumerate(values)).encode()


def _step(inst, values, token="d-0", ts0=NOW):
    inst.dispatcher.ingest_wire_lines(_lines(values, token, ts0))
    inst.dispatcher.flush()


def _counters(inst):
    c = inst.metrics.snapshot()["counters"]
    return c.get("pipeline.steps", 0), c.get("pipeline.steps_donated", 0)


def _rows(inst):
    # a copy: on the CPU a zero-copy view of the carry is an external
    # reference, and XLA copies a donated buffer that has one
    return np.array(inst.device_state.current_packed.rows, copy=True)


@pytest.mark.parametrize("donate", [False, True])
def test_the_step_consumes_the_epoch_only_where_it_donates(make_inst,
                                                           donate):
    """(a) The epoch a step was handed is deleted where the step donates
    and intact where it copies; the manager holds the step's output
    either way, and ``pipeline.steps_donated`` counts every step of the
    donating instance and none of the other (``wire_donated_step_share``
    reads 100 and 0)."""
    inst = make_inst(donate)
    steps0, donated0 = _counters(inst)
    epoch = inst.device_state.current_packed
    _step(inst, [1.0, 2.0, 3.0])
    assert epoch.rows.is_deleted() is donate
    held = inst.device_state.current_packed
    assert held is not epoch and not held.rows.is_deleted()
    assert inst.device_state.get_device_state("d-0")[
        "last_event_ts_s"] == NOW + 2
    steps1, donated1 = _counters(inst)
    assert steps1 > steps0
    assert donated1 - donated0 == (steps1 - steps0 if donate else 0)


def test_the_donated_rows_are_the_copying_steps_byte_for_byte(make_inst):
    """(a) The same plans through both programs leave the same carry."""
    rows = {}
    for donate in (False, True):
        inst = make_inst(donate)
        for k in range(4):
            _step(inst, [float(k), float(k) * 1.5, 7.25], token=f"d-{k}",
                  ts0=NOW + 10 * k)
        _step(inst, [9.0, 8.0], token="d-1", ts0=NOW + 100)
        rows[donate] = _rows(inst)
    assert rows[True].shape == rows[False].shape
    np.testing.assert_array_equal(rows[True], rows[False])


def test_readers_and_sweeps_race_the_donated_steps(make_inst):
    """(b) A presence-sweep thread and a ``current`` /
    ``get_device_state`` reader run against 200 donated steps: neither
    touches a deleted buffer, and the flags the sweeps set on the two
    silent devices survive every step of the other two."""
    inst = make_inst(True)
    mgr = inst.device_state
    for k in (2, 3):                 # silent since TS0
        _step(inst, [1.0], token=f"d-{k}", ts0=TS0)
    stop = threading.Event()
    errors = []

    def sweeper():
        try:
            while not stop.is_set():
                mgr.apply_presence_sweep(NOW, MISSING_AFTER)
        except Exception as e:       # pragma: no cover - the failure
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                np.asarray(mgr.current.presence_missing)
                mgr.get_device_state("d-0")
                mgr.missing_device_ids()
        except Exception as e:       # pragma: no cover - the failure
            errors.append(e)

    threads = [threading.Thread(target=sweeper),
               threading.Thread(target=reader)]
    for t in threads:
        t.start()
    try:
        steps0, donated0 = _counters(inst)
        for i in range(200):
            _step(inst, [float(i)], token=f"d-{i % 2}", ts0=NOW + i)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors
    steps1, donated1 = _counters(inst)
    assert steps1 - steps0 >= 200
    assert donated1 - donated0 == steps1 - steps0
    mgr.apply_presence_sweep(NOW, MISSING_AFTER)
    assert mgr.missing_device_ids() == [2, 3]
    assert mgr.get_device_state("d-1")["last_event_ts_s"] == NOW + 199


@pytest.mark.parametrize("fault", ["transient", "poison"])
def test_a_device_fault_leaves_the_epoch_whole(make_inst, fault):
    """(c) A fault fired at ``device.dispatch`` raises before the donated
    program is enqueued, so the epoch stays whole; the retry and the
    bisect read it again through the hand-off and every clean row
    commits (a transient fault: all of them; poison rows: all but
    those, which dead-letter)."""
    inst = make_inst(True)
    _step(inst, [0.5], token="d-3", ts0=NOW - 1)
    if fault == "transient":
        faults.device_inject("device.dispatch", times=1)
        values, clean = [1.0, 2.0, 3.0], 3
    else:
        faults.device_inject("device.dispatch", times=None,
                             when_nonfinite=True)
        values, clean = [1.0, float("nan"), 3.0, float("nan"), 5.0], 3
    stored0 = inst.event_store.total_events
    _step(inst, values)
    fired = faults.device_fired("device.dispatch")
    faults.device_clear()
    inst.event_store.flush()
    assert fired >= 1
    assert inst.event_store.total_events - stored0 == clean
    held = inst.device_state.current_packed
    assert not held.rows.is_deleted()
    assert inst.device_state.get_device_state("d-3")[
        "last_event_ts_s"] == NOW - 1
    assert inst.device_state.get_device_state("d-0")[
        "last_event_ts_s"] == NOW + len(values) - 1
    c = inst.metrics.snapshot()["counters"]
    assert c.get("device.fault.step_faults", 0) == 1
    poison = c.get("device.fault.poison_rows", 0)
    assert poison == len(values) - clean


def test_the_warm_up_leaves_the_epoch_as_it_was(make_inst):
    """(d) ``_warm_programs`` steps the live epoch through the hand-off
    with zero valid rows: the state is byte for byte what it was and
    the manager's carry is readable."""
    inst = make_inst(True)
    _step(inst, [4.0, 5.0], token="d-2")
    before = _rows(inst)
    epoch = inst.device_state.current_packed
    inst.dispatcher._warm_programs()
    assert inst.dispatcher.warm_error is None
    assert epoch.rows.is_deleted()       # it went through a donated step
    np.testing.assert_array_equal(_rows(inst), before)
    assert inst.device_state.get_device_state("d-2")[
        "last_event_ts_s"] == NOW + 1

"""Batch operations + schedule management.

Reference parity: BatchOperationManager element fan-out/throttle/status,
group expansion, and Quartz-style simple/cron triggers firing command jobs.
"""

import time

import pytest

from sitewhere_tpu.commands import (
    CallbackDeliveryProvider,
    CommandDestination,
    CommandProcessor,
    JsonCommandEncoder,
    TopicParameterExtractor,
)
from sitewhere_tpu.ids import IdentityMap
from sitewhere_tpu.services.batch_ops import (
    BatchOperationManager,
    EL_FAILED,
    EL_SUCCEEDED,
    OP_DONE,
    OP_DONE_ERRORS,
)
from sitewhere_tpu.services.common import (
    EntityNotFound,
    SearchCriteria,
    ValidationError,
)
from sitewhere_tpu.services.device_management import (
    DeviceGroupElement,
    DeviceManagement,
    RegistryMirror,
)
from sitewhere_tpu.services.schedules import CronSpec, ScheduleManager

from time_limit import wait_until


@pytest.fixture()
def stack():
    dm = DeviceManagement("default", IdentityMap(capacity=1024), RegistryMirror(1024))
    dm.create_device_type(token="thermo", name="T")
    dm.create_device_command(
        "thermo", token="ping", name="ping", parameters=[("n", "int32", False)]
    )
    for i in range(5):
        dm.create_device(token=f"d-{i}", device_type="thermo")
        if i != 4:  # d-4 left unassigned → element failure path
            dm.create_device_assignment(token=f"a-{i}", device=f"d-{i}")
    delivered = []
    proc = CommandProcessor(
        dm,
        destinations=[
            CommandDestination(
                "cb", JsonCommandEncoder(), TopicParameterExtractor(),
                CallbackDeliveryProvider(lambda ex, p, prm: delivered.append(prm["topic"])),
            )
        ],
    )
    return dm, proc, delivered


def test_batch_invocation_over_devices(stack):
    dm, proc, delivered = stack
    mgr = BatchOperationManager(dm, proc)
    op = mgr.create_batch_command_invocation(
        "ping", {"n": 1}, devices=[f"d-{i}" for i in range(5)]
    )
    mgr.process_now(op.token)
    assert op.status == OP_DONE_ERRORS  # d-4 has no assignment
    counts = op.counts
    assert counts[EL_SUCCEEDED] == 4 and counts[EL_FAILED] == 1
    assert len(delivered) == 4
    failed = mgr.list_elements(op.token, status=EL_FAILED)
    assert failed.total == 1 and failed.results[0].device == "d-4"
    assert mgr.get_operation(op.token).finished_s is not None


def test_batch_group_expansion_and_worker(stack):
    dm, proc, delivered = stack
    dm.create_device_group(token="fleet", name="Fleet")
    dm.add_device_group_elements(
        "fleet", [DeviceGroupElement(device="d-0"), DeviceGroupElement(device="d-1")]
    )
    mgr = BatchOperationManager(dm, proc)
    mgr.start()
    try:
        op = mgr.create_batch_command_invocation("ping", devices=["d-1"], group="fleet")
        # devices de-duplicated: d-1 appears once
        assert len(op.elements) == 2
        assert mgr.wait_idle(5)
        assert op.status == OP_DONE
    finally:
        mgr.stop()


def test_batch_throttle_paces(stack):
    dm, proc, delivered = stack
    mgr = BatchOperationManager(dm, proc, throttle_delay_ms=20)
    op = mgr.create_batch_command_invocation("ping", devices=["d-0", "d-1", "d-2"])
    t0 = time.monotonic()
    mgr.process_now(op.token)
    assert time.monotonic() - t0 >= 0.04  # 2 inter-element gaps × 20ms

    with pytest.raises(ValidationError):
        mgr.create_batch_command_invocation("ping", devices=[])
    with pytest.raises(EntityNotFound):
        mgr.get_operation("nope")


def test_cron_spec():
    spec = CronSpec.parse("*/15 3 * * *")
    assert spec.minutes == frozenset({0, 15, 30, 45})
    assert spec.hours == {3}
    base = time.mktime((2026, 7, 29, 3, 7, 0, 0, 0, -1))
    nxt = spec.next_fire(int(base))
    t = time.localtime(nxt)
    assert (t.tm_hour, t.tm_min) == (3, 15)
    # range + list
    spec2 = CronSpec.parse("0 9-17 * * 0-4")
    assert 13 in spec2.hours and 6 not in spec2.dow
    with pytest.raises(ValidationError):
        CronSpec.parse("61 * * * *")
    with pytest.raises(ValidationError):
        CronSpec.parse("* * *")


def test_cron_dow_is_cron_numbering():
    # Standard cron: 0 (and 7) = Sunday.  2026-08-02 is a Sunday.
    sunday_noon = CronSpec.parse("0 12 * * 0")
    base = int(time.mktime((2026, 8, 2, 0, 0, 0, 0, 0, -1)))
    t = time.localtime(sunday_noon.next_fire(base))
    assert (t.tm_year, t.tm_mon, t.tm_mday, t.tm_hour) == (2026, 8, 2, 12)
    assert CronSpec.parse("0 12 * * 7").dow == CronSpec.parse("0 12 * * 0").dow
    # Mon-Fri must match a Monday (2026-08-03).
    weekdays = CronSpec.parse("0 9 * * 1-5")
    t = time.localtime(weekdays.next_fire(base))
    assert (t.tm_mday, t.tm_wday) == (3, 0)


def test_cron_dom_dow_or_semantics():
    # Vixie cron: "0 0 13 * 5" fires on the 13th OR any Friday.
    spec = CronSpec.parse("0 0 13 * 5")
    base = int(time.mktime((2026, 8, 4, 0, 30, 0, 0, 0, -1)))  # Tue Aug 4
    t = time.localtime(spec.next_fire(base))
    assert (t.tm_mday, t.tm_wday) == (7, 4)  # Fri Aug 7 (before the 13th)
    t2 = time.localtime(spec.next_fire(int(time.mktime((2026, 8, 10, 1, 0, 0, 0, 0, -1)))))
    assert t2.tm_mday == 13  # Thu Aug 13 (before Fri the 14th)
    # Restricted dom + star dow still ANDs.
    only13 = CronSpec.parse("0 0 13 * *")
    t3 = time.localtime(only13.next_fire(base))
    assert t3.tm_mday == 13
    # "*/2" counts as a star field for the day rule (Vixie): ANDs with dow.
    stepped = CronSpec.parse("0 0 */2 * 1")
    t4 = time.localtime(stepped.next_fire(base))
    assert t4.tm_wday == 0 and t4.tm_mday % 2 == 1  # a Monday on an odd day


def test_cron_step_and_reversed_range():
    # "5/15" = start at 5, step 15 to field max (standard cron).
    assert CronSpec.parse("5/15 * * * *").minutes == frozenset({5, 20, 35, 50})
    with pytest.raises(ValidationError):
        CronSpec.parse("0 17-9 * * *")


def test_schedule_simple_fire_and_repeat_limit():
    fired = []
    mgr = ScheduleManager(executors={"CommandInvocation": lambda job: fired.append(job.token)})
    s = mgr.create_schedule(token="s-1", trigger_type="Simple", interval_s=60, repeat_count=1)
    mgr.create_job(token="j-1", schedule="s-1", job_type="CommandInvocation")
    # fire 1 (fires==0 → due now)
    assert mgr.due_schedules(at_s=mgr._next["s-1"]) == ["s-1"]
    mgr.fire("s-1", at_s=1000)
    assert fired == ["j-1"]
    assert mgr._next["s-1"] == 1060  # next fire scheduled
    mgr.fire("s-1", at_s=1060)
    # repeat_count=1 → 2 fires total, then unscheduled
    assert "s-1" not in mgr._next
    assert mgr.get_job("j-1").fire_count == 2


def test_schedule_end_window_and_cron_next():
    mgr = ScheduleManager()
    s = mgr.create_schedule(
        token="s-2", trigger_type="Cron", cron="0 0 * * *", end_s=0
    )
    # end before any fire → never scheduled
    assert "s-2" not in mgr._next


def test_job_failure_isolated():
    calls = []

    def boom(job):
        calls.append(job.token)
        raise RuntimeError("job bug")

    mgr = ScheduleManager(executors={"CommandInvocation": boom})
    mgr.create_schedule(token="s-3", trigger_type="Simple", interval_s=10)
    mgr.create_job(token="j-3", schedule="s-3", job_type="CommandInvocation")
    assert mgr.fire("s-3") == 0  # failed job not counted
    assert calls == ["j-3"]
    assert mgr.get_job("j-3").fire_count == 0


def test_never_matching_cron_is_cheap():
    spec = CronSpec.parse("0 0 31 2 *")  # Feb 31 never exists
    t0 = time.monotonic()
    assert spec.next_fire(1_753_800_000) is None
    assert time.monotonic() - t0 < 0.5  # day-skipping, not minute scanning


def test_json_encoder_bytes_base64(stack):
    import base64
    import json as _json

    dm, proc, delivered = stack
    dm.create_device_command(
        "thermo", token="blob", name="blob", parameters=[("data", "bytes", True)]
    )
    from sitewhere_tpu.commands import CommandInvocation

    payloads = []
    from sitewhere_tpu.commands import (
        CallbackDeliveryProvider, CommandDestination, JsonCommandEncoder,
        TopicParameterExtractor,
    )
    proc.add_destination  # (uses fixture's processor with its cb destination)
    proc2 = type(proc)(dm, destinations=[CommandDestination(
        "cb", JsonCommandEncoder(), TopicParameterExtractor(),
        CallbackDeliveryProvider(lambda ex, p, prm: payloads.append(p)))])
    assert proc2.invoke(CommandInvocation(
        command_token="blob", target_assignment="a-0",
        parameter_values={"data": b"\x00\x01\x02"}))
    doc = _json.loads(payloads[0])
    assert base64.b64decode(doc["parameters"]["data"]) == b"\x00\x01\x02"


def test_int_range_validation(stack):
    dm, proc, delivered = stack
    dm.create_device_command(
        "thermo", token="i32", name="i32", parameters=[("n", "int32", True)]
    )
    from sitewhere_tpu.commands import CommandInvocation

    assert not proc.invoke(CommandInvocation(
        command_token="i32", target_assignment="a-0",
        parameter_values={"n": 2**40}))  # out of int32 range → dead-letter
    assert proc.invoke(CommandInvocation(
        command_token="i32", target_assignment="a-0",
        parameter_values={"n": 1}))


def test_interrupted_batch_resumes(stack):
    dm, proc, delivered = stack
    mgr = BatchOperationManager(dm, proc)
    op = mgr.create_batch_command_invocation("ping", devices=["d-0", "d-1", "d-2"])
    mgr._stop.set()  # simulate shutdown before processing
    mgr.process_now(op.token)
    assert op.status == "Unprocessed"  # not falsely finished
    mgr._stop.clear()
    mgr.process_now(op.token)
    assert op.status == OP_DONE
    assert op.counts[EL_SUCCEEDED] == 3
    assert len(delivered) == 3  # no element double-delivered


def test_ticker_thread_fires():
    fired = []
    mgr = ScheduleManager(
        executors={"CommandInvocation": lambda job: fired.append(1)}, tick_s=0.02
    )
    mgr.create_schedule(token="s-t", trigger_type="Simple", interval_s=3600)
    mgr.create_job(token="j-t", schedule="s-t", job_type="CommandInvocation")
    mgr.start()
    try:
        assert wait_until(lambda: fired)  # first fire: at/near creation
    finally:
        mgr.stop()


def test_delete_schedule_cascades_jobs():
    mgr = ScheduleManager()
    mgr.create_schedule(token="s-4", trigger_type="Simple", interval_s=5)
    mgr.create_job(token="j-4", schedule="s-4")
    mgr.delete_schedule("s-4")
    with pytest.raises(EntityNotFound):
        mgr.get_job("j-4")
    with pytest.raises(EntityNotFound):
        mgr.create_job(schedule="s-4")

"""A wire payload has a tenant, given by its source.

``ingest_wire_lines(payload, tenant=...)`` on both lanes of the wire
intake (the native fill-direct scanner and the classic column decode),
the journal record that keeps the tenant (and both replays of it), and
the system against the benchmark's plain reference of kind
``tenant-topics-presence`` with three tenants of unequal size.
"""

import json
import struct
import zlib

import numpy as np
import pytest

from sitewhere_tpu.ingest.journal import CorruptJournal, Journal, JournalReader
from sitewhere_tpu.instance import Instance
from sitewhere_tpu.runtime.config import Config
from sitewhere_tpu.runtime.overload import OverloadShed, OverloadState
from sitewhere_tpu.schema import AlertLevel, ComparisonOp, EventType

TENANTS = {"acme": 12, "globex": 6, "initech": 3}     # devices a tenant
THRESHOLDS = {"acme": 90.0, "globex": 60.0, "initech": 30.0}
T0 = 1_753_800_000


def _payload(tokens, values, ts_ms, kind="Measurement"):
    if kind == "Measurement":
        return "\n".join(json.dumps({
            "deviceToken": t, "type": kind,
            "request": {"name": "temp", "value": float(v), "eventDate": ts_ms},
        }) for t, v in zip(tokens, values)).encode()
    return "\n".join(json.dumps({
        "deviceToken": t, "type": kind,
        "request": {"latitude": float(v), "longitude": 1.0,
                    "eventDate": ts_ms},
    }) for t, v in zip(tokens, values)).encode()


@pytest.fixture
def inst(tmp_path):
    cfg = Config({
        "instance": {"id": "tenant-wire", "data_dir": str(tmp_path / "d")},
        "pipeline": {"width": 64, "registry_capacity": 128,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "checkpoint": {"interval_s": 0},
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    inst.fleet = {}
    for name, n in TENANTS.items():
        inst.tenants.create_tenant(token=name, name=name.title(),
                                   auth_token=f"{name}-auth-token-123")
        dm = inst.engines.get_engine(name).device_management
        dm.create_device_type(token="sensor", name="Sensor")
        inst.fleet[name] = [f"{name}-d{i}" for i in range(n)]
        for token in inst.fleet[name]:
            dm.create_device(token=token, device_type="sensor")
            dm.create_device_assignment(device=token)
        inst.rules.create_rule(
            mtype=None, op=ComparisonOp.GT, threshold=THRESHOLDS[name],
            alert_type=f"hot-{name}", alert_level=AlertLevel.WARNING,
            tenant=name)
    yield inst
    inst.stop()
    inst.terminate()


def _tid(inst, name):
    return int(inst.engines.get_engine(name).tenant_id)


def _drain(inst):
    for _ in range(3):
        inst.dispatcher.flush()


def _stored(inst, tenant_id, event_type=EventType.MEASUREMENT):
    return inst.event_store.query(tenant_id=tenant_id,
                                  event_type=int(event_type)).total


def _unregistered_letters(inst):
    docs = [json.loads(doc) for _, doc in inst.dead_letters.scan(0)]
    return [d for d in docs if d.get("kind") == "unregistered"]


def _lands_in_the_tenant(inst):
    d = inst.dispatcher
    n = d.ingest_wire_lines(_payload(inst.fleet["globex"], range(6), T0 * 1000),
                            tenant="globex")
    _drain(inst)
    snap = d.metrics_snapshot()
    assert n == 6 and snap["accepted"] == 6 and snap["unregistered"] == 0
    assert _stored(inst, _tid(inst, "globex")) == 6
    assert _stored(inst, _tid(inst, "acme")) == 0
    assert _stored(inst, _tid(inst, "default")) == 0
    counters = inst.metrics.snapshot()["counters"]
    assert counters["ingest.wire_rows_tenant"] == 6
    assert counters["ingest.wire_rows"] == 6
    assert inst.ingest_journal.read_record(0)[1] == "globex"


def _wrong_tenant_is_refused(inst):
    d = inst.dispatcher
    d.ingest_wire_lines(_payload(inst.fleet["globex"], range(6), T0 * 1000),
                        tenant="acme")
    _drain(inst)
    snap = d.metrics_snapshot()
    assert snap["unregistered"] == 6 and snap["accepted"] == 0
    assert inst.event_store.total_events == 0
    letters = _unregistered_letters(inst)
    assert sum(doc["count"] for doc in letters) == 6
    assert letters[0]["tenant_ids"] == [_tid(inst, "acme")]
    assert inst.registration.registered == 0
    state = inst.device_state.get_device_state(inst.fleet["globex"][0])
    assert state["last_event_type"] is None


def _a_token_no_tenant_has(inst):
    d = inst.dispatcher
    d.ingest_wire_lines(_payload(inst.fleet["acme"], range(12), T0 * 1000),
                        tenant="nobody")
    _drain(inst)
    snap = d.metrics_snapshot()
    assert snap["unregistered"] == 12 and snap["accepted"] == 0
    assert inst.event_store.total_events == 0
    assert _stored(inst, _tid(inst, "default")) == 0
    assert sum(doc["count"] for doc in _unregistered_letters(inst)) == 12


def _a_shed_is_billed_to_the_tenant(inst):
    d = inst.dispatcher
    inst.overload.force(OverloadState.SHEDDING, "test")
    payload = _payload(inst.fleet["globex"], range(6), T0 * 1000)
    with pytest.raises(OverloadShed):
        d.ingest_wire_lines(payload, source_id="gw-7", tenant="globex")
    docs = [json.loads(doc) for _, doc in inst.dead_letters.scan(0)]
    shed = [doc for doc in docs if doc.get("kind") == "intake-shed"]
    assert len(shed) == 1 and shed[0]["tenant"] == "globex"
    assert shed[0]["source"] == "gw-7"
    counters = inst.metrics.snapshot()["counters"]
    assert counters["tenant.shed.globex"] == 6
    assert counters.get("tenant.shed.default", 0) == 0
    assert inst.ingest_journal.end_offset == 0      # shed before the journal


def _the_default_is_unchanged(inst):
    d = inst.dispatcher
    dm = inst.device_management
    dm.create_device_type(token="sensor", name="Sensor")
    for i in range(4):
        dm.create_device(token=f"d-{i}", device_type="sensor")
        dm.create_device_assignment(device=f"d-{i}")
    n = d.ingest_wire_lines(_payload([f"d-{i}" for i in range(4)], range(4),
                                     T0 * 1000))
    _drain(inst)
    assert n == 4 and d.metrics_snapshot()["accepted"] == 4
    assert _stored(inst, _tid(inst, "default")) == 4
    counters = inst.metrics.snapshot()["counters"]
    assert counters["ingest.wire_rows_tenant"] == 0
    assert counters["ingest.wire_rows"] == 4
    # a default payload's record is the old encoding, byte for byte
    assert inst.ingest_journal.read_record(0)[1] == "default"


CASES = {
    "lands-in-the-tenant": _lands_in_the_tenant,
    "wrong-tenant-refused-and-dead-lettered": _wrong_tenant_is_refused,
    "token-no-tenant-has": _a_token_no_tenant_has,
    "shed-billed-to-the-tenant": _a_shed_is_billed_to_the_tenant,
    "default-unchanged": _the_default_is_unchanged,
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("lane", ["fill-direct", "classic"])
def test_wire_intake_takes_the_payloads_tenant(inst, lane, case):
    """One parameter through the path both lanes share."""
    inst.dispatcher._fill_enabled = lane == "fill-direct"
    CASES[case](inst)
    copied = inst.metrics.snapshot()["counters"]["pipeline.bytes_copied.decode"]
    assert (copied == 0) == (lane == "fill-direct")


# -- a refused row: the default tenant's registers, another tenant's dies ------


def _scalar(inst, payload, tenant=None):
    from sitewhere_tpu.ingest.decoders import JsonLinesDecoder

    reqs = JsonLinesDecoder()(payload)
    for r in reqs:
        if tenant is not None:
            r.metadata = dict(r.metadata or {}, tenant=tenant)
    inst.dispatcher.ingest_many(reqs, payload, "src")


@pytest.mark.parametrize("path", ["scalar", "wire"])
def test_an_unknown_device_of_the_default_tenant_still_auto_registers(
        inst, path):
    inst.device_management.create_device_type(token="sensor", name="Sensor")
    inst.registration.default_device_type = "sensor"
    payload = _payload(["ghost-1"], [7.0], T0 * 1000)
    if path == "scalar":
        _scalar(inst, payload)
    else:
        inst.dispatcher.ingest_wire_lines(payload)
    _drain(inst)            # refused, registered, replayed, taken
    snap = inst.dispatcher.metrics_snapshot()
    assert snap["unregistered"] == 1 and snap["replayed"] == 1
    assert snap["accepted"] == 1
    assert inst.registration.registered == 1
    assert inst.device_management.get_device("ghost-1") is not None
    assert _stored(inst, _tid(inst, "default")) == 1
    assert _unregistered_letters(inst) == []


@pytest.mark.parametrize("token", ["globex-d0", "ghost-2"],
                         ids=["another-tenants-device", "unknown-device"])
def test_a_metadata_tenant_row_refused_dead_letters_and_never_registers(
        inst, token):
    """The scalar path's ``metadata.tenant``: a row for ``acme`` naming a
    device ``acme`` does not own is refused for good, whoever owns it."""
    from sitewhere_tpu.services.common import EntityNotFound

    inst.device_management.create_device_type(token="sensor", name="Sensor")
    inst.registration.default_device_type = "sensor"
    _scalar(inst, _payload([token], [7.0], T0 * 1000), tenant="acme")
    _drain(inst)
    snap = inst.dispatcher.metrics_snapshot()
    assert snap["unregistered"] == 1 and snap["accepted"] == 0
    assert snap["replayed"] == 0 and inst.registration.registered == 0
    assert inst.event_store.total_events == 0
    letters = _unregistered_letters(inst)
    assert [doc["count"] for doc in letters] == [1]
    assert letters[0]["tenant_ids"] == [_tid(inst, "acme")]
    with pytest.raises(EntityNotFound):
        inst.device_management.get_device(token)   # not under default


# -- the journal record ------------------------------------------------------


def test_a_tenant_rides_the_journal_record(tmp_path):
    j = Journal(str(tmp_path), fsync_every=0)
    assert j.append(b"plain") == 0
    assert j.append(b"tenant's", tenant="globex") == 1
    assert j.append(b"plain again", tenant="default") == 2
    assert list(j.scan(0)) == [(0, b"plain"), (1, b"tenant's"),
                               (2, b"plain again")]
    assert list(j.records(0)) == [
        (0, b"plain", "default"), (1, b"tenant's", "globex"),
        (2, b"plain again", "default")]
    assert j.read_one(1) == b"tenant's"
    assert j.read_record(1) == (b"tenant's", "globex")
    reader = JournalReader(j, "g")
    assert reader.poll_records(10)[1] == (1, b"tenant's", "globex")
    j.close()
    # reopened: counted, indexed and read the same
    j = Journal(str(tmp_path), fsync_every=0)
    assert j.end_offset == 3
    assert j.read_record(1) == (b"tenant's", "globex")
    j.close()


def test_a_record_in_the_old_encoding_still_reads(tmp_path):
    """Bytes as the journal wrote them before a record could carry a
    tenant: ``[u32 len][u32 crc][payload]``, bit 31 of ``len`` clear."""
    j = Journal(str(tmp_path), fsync_every=0)
    path = j._file.name
    j.close()
    with open(path, "ab") as f:
        for payload in (b"old-0", b"old-1"):
            f.write(struct.pack("<II", len(payload), zlib.crc32(payload)))
            f.write(payload)
    j = Journal(str(tmp_path), fsync_every=0)
    assert j.end_offset == 2
    assert list(j.records(0)) == [(0, b"old-0", "default"),
                                             (1, b"old-1", "default")]
    # and a default payload is still written that way, byte for byte
    j.append(b"new-2")
    j.close()
    with open(path, "rb") as f:
        tail = f.read()[-(8 + 5):]
    assert tail == struct.pack("<II", 5, zlib.crc32(b"new-2")) + b"new-2"


@pytest.mark.parametrize("cut", [3, 9, 14])
def test_a_torn_tail_of_a_tenant_record_is_truncated(tmp_path, cut):
    """Torn in the header, in the tenant, in the payload."""
    j = Journal(str(tmp_path), fsync_every=0)
    j.append(b"whole", tenant="acme")
    path = j._file.name
    j.append(b"torn-record", tenant="globex")
    j.close()
    whole = 8 + 1 + 4 + 5
    with open(path, "r+b") as f:
        f.truncate(whole + cut)
    j = Journal(str(tmp_path), fsync_every=0)
    assert j.end_offset == 1
    assert list(j.records(0)) == [(0, b"whole", "acme")]
    assert j.append(b"next", tenant="globex") == 1
    assert j.read_record(1) == (b"next", "globex")
    j.close()


def test_the_crc_covers_the_tenant(tmp_path):
    j = Journal(str(tmp_path), fsync_every=0)
    j.append(b"payload", tenant="acme")
    j.append(b"after")
    path = j._file.name
    j.close()
    with open(path, "r+b") as f:
        f.seek(8 + 1)                 # first byte of the tenant's token
        f.write(b"b")
    with pytest.raises(CorruptJournal):
        Journal(str(tmp_path), fsync_every=0)


@pytest.mark.parametrize("replay", ["columnar", "scalar"])
def test_a_tenant_payload_replays_into_its_tenant(inst, replay):
    """The strict measurement scanner takes the first kind of payload
    back in (columnar replay); it bails on a Location line, so the
    second replays through the scalar decoder.  Either way the rows land
    in the tenant the record names."""
    d = inst.dispatcher
    kind = "Measurement" if replay == "columnar" else "Location"
    payload = _payload(inst.fleet["globex"], range(6), T0 * 1000, kind)
    assert d.ingest_wire_lines(payload, tenant="globex") == 6
    _drain(inst)
    before = d.metrics_snapshot()
    assert before["accepted"] == 6 and before["unregistered"] == 0
    assert inst.ingest_journal.read_record(0) == (payload, "globex")
    assert d.replay_journal(from_offset=0) == 6
    _drain(inst)
    after = d.metrics_snapshot()
    assert after["processed"] - before["processed"] == 6
    assert after["accepted"] - before["accepted"] == 6
    assert after["unregistered"] == 0
    # below the committed offset: replayed for state, not stored twice
    assert inst.event_store.total_events == 6
    assert _stored(inst, _tid(inst, "globex"),
                   EventType.MEASUREMENT if replay == "columnar"
                   else EventType.LOCATION) == 6


# -- against the plain reference ---------------------------------------------


def test_three_tenants_against_the_plain_reference(inst):
    """Counts by tenant, alerts by the tenant's own rule only, reports of
    silent devices once each: the system through the wire path against
    ``benchmarks/configs/references/tenant-topics-presence.py``."""
    from benchmarks import cells

    ref = cells.load_module(cells.reference_file("tenant-topics-presence"))
    assert (ref.MEASUREMENT, ref.ALERT, ref.STATE_CHANGE) == (
        int(EventType.MEASUREMENT), int(EventType.ALERT),
        int(EventType.STATE_CHANGE))
    rng = np.random.default_rng(7)
    d = inst.dispatcher
    names = list(TENANTS)
    ids = {name: _tid(inst, name) for name in names}
    owner_of = np.full(128, -1, np.int32)
    handle = {}
    for name in names:
        for token in inst.fleet[name]:
            handle[token] = int(inst.identity.device.lookup(token))
            owner_of[handle[token]] = ids[name]
    missing_after = int(inst.presence.missing_after_s)
    bodies, named, stamps = [], [], []

    def body(tokens, claimed, ts_s):
        bodies.append({
            "dev": np.asarray([handle[t] for t in tokens], np.int32),
            "tenant": np.full(len(tokens), ids[claimed], np.int32),
            "value": np.round(rng.uniform(0, 100, len(tokens)), 3)
            .astype(np.float32)})
        named.append((tokens, claimed))
        stamps.append(ts_s)

    # the last device of each tenant is heard once, long ago: silent
    for name in names:
        body(inst.fleet[name][-1:], name, T0 - missing_after - 50)
    for r in range(4):
        for name in names:
            body(inst.fleet[name][:-1], name, T0 + r)
    body(inst.fleet["globex"][:-1], "acme", T0 + 9)       # refused whole
    for seq, (b, (tokens, claimed)) in enumerate(zip(bodies, named)):
        d.ingest_wire_lines(
            _payload(tokens, b["value"].tolist(), stamps[seq] * 1000 + seq),
            tenant=claimed)
    _drain(inst)
    for now in (T0 + 20, T0 + 40):
        inst.presence.sweep_once(now_s=now)
    _drain(inst)

    rules = {ids[name]: [{"op": "GT", "threshold": THRESHOLDS[name]}]
             for name in names}
    sent = list(range(len(bodies)))
    want = ref.expected_counts(bodies, sent, owner_of, rules)
    newest = ref.newest_events(bodies, [(s, s) for s in sent],
                               lambda seq: stamps[seq], owner_of)
    silent = ref.reported_missing(newest, missing_after, T0 + 40, T0 + 41)
    assert silent == {handle[inst.fleet[name][-1]] for name in names}
    assert want["refused"] == 5
    snap = d.metrics_snapshot()
    assert snap["unregistered"] == want["refused"]
    alerts = sum(want["alerts"].values())
    assert snap["threshold_alerts"] == alerts > 0
    for name in names:
        assert _stored(inst, ids[name]) == want["events"][ids[name]], name
        assert _stored(inst, ids[name], EventType.ALERT) \
            == want["alerts"][ids[name]], name
    # thresholds 90 / 60 / 30: a rule that leaks moves another's count
    assert want["alerts"][ids["acme"]] < want["alerts"][ids["globex"]]
    from sitewhere_tpu.services.common import SearchCriteria

    reports = inst.event_store.query(
        SearchCriteria(page_size=0),
        event_type=int(EventType.STATE_CHANGE)).results
    assert sorted(r.device_id for r in reports) == sorted(silent)
    assert all(r.tenant_id == owner_of[r.device_id] for r in reports)
    assert sum(doc["count"] for doc in _unregistered_letters(inst)) == 5
    for dev, (ts_s, value) in newest.items():
        row = inst.device_state.get_device_state_by_id(dev)
        assert row["last_event_ts_s"] == ts_s
        assert row["presence_missing"] == (dev in silent)

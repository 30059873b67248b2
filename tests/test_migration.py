"""Ownership migration on cluster membership change.

Reference behaviors covered: Kafka consumer rebalance (partition
responsibility moves with membership, streams resume from committed
offsets) and ApiDemux discovery add/remove — reshaped as rendezvous
remap + record handoff + spool requeue (``rpc/migration.py``,
``HostForwarder.apply_membership``).
"""

import json
import socket

import numpy as np
import pytest

from sitewhere_tpu.instance import Instance
from sitewhere_tpu.rpc import owning_process
from sitewhere_tpu.runtime.config import Config
from sitewhere_tpu.services.common import SearchCriteria

from time_limit import wait_until


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def make_inst(tmp_path, p, ports, peers, instance_id=None):
    cfg = Config({
        "instance": {"id": instance_id or f"mig{p}",
                     "data_dir": str(tmp_path / (instance_id or f"h{p}"))},
        "pipeline": {"width": 128, "registry_capacity": 1024,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "rpc": {"server": {"enabled": True, "host": "127.0.0.1",
                           "port": ports[p]},
                "process_id": p, "peers": peers,
                "forward_deadline_ms": 10.0},
        "security": {"jwt_secret": "mig-secret"},
        "registration": {"default_device_type": "sensor"},
    }, apply_env=False)
    return Instance(cfg)


def seed(inst, tokens):
    inst.device_management.create_device_type(token="sensor", name="S")
    for tok in tokens:
        inst.device_management.create_device(token=tok,
                                             device_type="sensor")
        inst.device_management.create_device_assignment(device=tok)


def tokens_owned_by(owner, n, count=40, prefix="dev"):
    return [f"{prefix}-{i}" for i in range(400)
            if owning_process(f"{prefix}-{i}", n) == owner][:count]


def test_state_row_export_import_newest_wins(tmp_path):
    from tests.test_instance import make_config, seed_device
    from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind

    a = Instance(make_config(tmp_path / "a"))
    b = Instance(make_config(tmp_path / "b"))
    for i in (a, b):
        i.start()
    try:
        seed_device(a, "dev-1")
        seed_device(b, "dev-1")
        for inst, value, ts in ((a, 30.0, 2000), (b, 10.0, 1000)):
            inst.dispatcher.ingest(DecodedRequest(
                kind=RequestKind.MEASUREMENT, device_token="dev-1",
                ts_s=ts, mtype="temp", value=value))
            inst.dispatcher.flush()
        da = int(a.identity.device.lookup("dev-1"))
        db = int(b.identity.device.lookup("dev-1"))
        row = a.device_state.export_row(da)
        assert row["last_event_ts_s"] == 2000
        # newer wins: b holds ts 1000 → import applies
        assert b.device_state.import_row(db, row) is True
        assert b.device_state.get_device_state("dev-1")[
            "last_event_ts_s"] == 2000
        # older loses: importing b's (now stale) copy back into a is a no-op
        stale = dict(row, last_event_ts_s=1500)
        assert a.device_state.import_row(da, stale) is False
        assert a.device_state.get_device_state("dev-1")[
            "last_event_ts_s"] == 2000
    finally:
        for i in (a, b):
            i.stop()
            i.terminate()


@pytest.mark.slow
def test_grow_membership_hands_off_records(tmp_path):
    """2 → 3 hosts: devices remapping to the new host arrive there with
    registry rows, assignments, and newest-wins state — and NEW traffic
    for them routes to the new owner."""
    ports = [free_port(), free_port(), free_port()]
    peers2 = [f"127.0.0.1:{p}" for p in ports[:2]]
    peers3 = [f"127.0.0.1:{p}" for p in ports]

    insts = [make_inst(tmp_path, p, ports, peers2) for p in range(2)]
    for inst in insts:
        inst.start()
    try:
        # devices owned by each of the two hosts under P=2
        toks = {p: tokens_owned_by(p, 2, count=30) for p in range(2)}
        for p, inst in enumerate(insts):
            seed(inst, toks[p])
        # stream one measurement per device so state exists
        for p, inst in enumerate(insts):
            lines = [json.dumps({
                "deviceToken": t, "type": "Measurement",
                "request": {"name": "t", "value": 42.0,
                            "eventDate": 5000}}).encode()
                for t in toks[p]]
            inst.forwarder.ingest_payload(b"\n".join(lines))
            inst.dispatcher.flush()

        # host 2 joins (fresh, knows the 3-list from its config)
        third = make_inst(tmp_path, 2, ports, peers3)
        third.start()
        third.device_management.create_device_type(token="sensor", name="S")
        summaries = [inst.apply_membership_change(peers3)
                     for inst in insts]

        moving = [t for p in range(2) for t in toks[p]
                  if owning_process(t, 3) == 2]
        assert moving, "test needs at least one remapping device"
        assert sum(s["moved"] for s in summaries) == len(moving)
        assert all(s["failed"] == 0 for s in summaries)

        for t in moving:
            # registry + assignment landed
            assert third.device_management.get_device(t) is not None
            assert third.device_management.get_active_assignment(t) is not None
            # state landed, newest-wins (ts 5000 from the stream)
            st = third.device_state.get_device_state(t)
            assert st["last_event_ts_s"] == 5000

        # NEW traffic for a moved device arriving at host 0 routes to 2
        probe = moving[0]
        line = json.dumps({
            "deviceToken": probe, "type": "Measurement",
            "request": {"name": "t", "value": 7.0,
                        "eventDate": 6000}}).encode()
        insts[0].forwarder.ingest_payload(line)

        def landed():
            insts[0].forwarder.flush(wait=True)
            third.dispatcher.flush()
            return third.device_state.get_device_state(probe)[
                "last_event_ts_s"] == 6000

        assert wait_until(landed)
        insts.append(third)
    finally:
        for inst in insts:
            inst.stop()
            inst.terminate()


@pytest.mark.slow
def test_kill_host_replace_with_new_loses_nothing(tmp_path):
    """The round-4 membership soak: host 2 of 3 dies mid-stream, a NEW
    host joins at a fresh endpoint.  No event loss: rows spooled for the
    dead host drain to its replacement (auto-registration re-mints the
    devices), and state queries for the remapped devices answer with
    the latest event."""
    ports = [free_port(), free_port(), free_port(), free_port()]
    peers_old = [f"127.0.0.1:{p}" for p in ports[:3]]
    # replacement host D takes INDEX 2 at a NEW endpoint
    peers_new = [f"127.0.0.1:{ports[0]}", f"127.0.0.1:{ports[1]}",
                 f"127.0.0.1:{ports[3]}"]

    insts = [make_inst(tmp_path, p, ports, peers_old) for p in range(3)]
    for inst in insts:
        inst.start()
    toks = {p: tokens_owned_by(p, 3, count=10) for p in range(3)}
    for p, inst in enumerate(insts):
        seed(inst, toks[p])

    def batch(i):
        lines = []
        for p in range(3):
            for t in toks[p][:5]:
                lines.append(json.dumps({
                    "deviceToken": t, "type": "Measurement",
                    "request": {"name": "t", "value": float(i),
                                "eventDate": 1000 + i}}).encode())
        return b"\n".join(lines)

    n_batches = 12
    replacement = None
    try:
        fwd = insts[0].forwarder
        for i in range(n_batches):
            if i == 4:
                # host 2 dies hard — its rows start spooling at host 0
                insts[2].stop()
                insts[2].terminate()
            if i == 8:
                # a NEW host joins at a fresh endpoint, same index
                replacement = make_inst(
                    tmp_path, 2,
                    [ports[0], ports[1], ports[3]], peers_new,
                    instance_id="replacement")
                replacement.start()
                # auto-registration mints against this default type
                replacement.device_management.create_device_type(
                    token="sensor", name="S")
                for inst in insts[:2]:
                    inst.apply_membership_change(peers_new)
            fwd.ingest_payload(batch(i))
            fwd.flush()

        def drained():
            fwd.flush(wait=True)
            return fwd.metrics()["pending"] == 0

        assert wait_until(drained)
        assert fwd.dead_lettered == 0

        # no event loss: every host-2-owned row sent AFTER its death is
        # queryable on the replacement (auto-registered from the stream)
        replacement.dispatcher.flush()
        replacement.event_store.flush()
        for t in toks[2][:5]:
            assert replacement.device_management.get_device(t) is not None
            st = replacement.device_state.get_device_state(t)
            # the final batch's eventDate made it through
            assert st["last_event_ts_s"] == 1000 + n_batches - 1
        total = replacement.event_store.query(
            SearchCriteria(page_size=0)).total
        # batches 4..11 were sent while host 2 was dead/replaced: every
        # one of their 5 host-2 rows must be stored on the replacement
        # (batches 0..3 landed on the original host 2 and died with it —
        # that is a host loss, not an event loss; at-least-once may also
        # deliver duplicates, hence >=)
        assert total >= (n_batches - 4) * 5
    finally:
        for inst in insts[:2]:
            inst.stop()
            inst.terminate()
        if replacement is not None:
            replacement.stop()
            replacement.terminate()


@pytest.mark.slow
def test_membership_change_over_rest(tmp_path):
    """The ops surface: POST /api/instance/cluster/membership applies
    the change (admin-only) and returns the handoff summary."""
    import base64
    import http.client

    from sitewhere_tpu.web import WebServer

    ports = [free_port(), free_port(), free_port()]
    peers2 = [f"127.0.0.1:{p}" for p in ports[:2]]
    peers3 = [f"127.0.0.1:{p}" for p in ports]
    insts = [make_inst(tmp_path, p, ports, peers2) for p in range(2)]
    for inst in insts:
        inst.start()
    toks = tokens_owned_by(0, 2, count=20)
    seed(insts[0], toks)
    third = None
    web = WebServer(insts[0], port=0)
    web.start()
    try:
        third = make_inst(tmp_path, 2, ports, peers3)
        third.start()
        third.device_management.create_device_type(token="sensor", name="S")

        def req(method, path, body=None, auth=None):
            conn = http.client.HTTPConnection("127.0.0.1", web.port,
                                              timeout=15)
            hdrs = {"Authorization": auth} if auth else {}
            conn.request(method, path,
                         json.dumps(body).encode() if body else None, hdrs)
            resp = conn.getresponse()
            data = resp.read()
            conn.close()
            return resp.status, (json.loads(data) if data else None)

        basic = base64.b64encode(b"admin:password").decode()
        st, body = req("POST", "/api/jwt", auth=f"Basic {basic}")
        admin = f"Bearer {body['token']}"

        # non-admin cannot rebalance the cluster
        insts[0].users.create_user(username="viewer", password="pw123456",
                                   authorities=[])
        st, body = req("POST", "/api/jwt", auth="Basic " + base64.b64encode(
            b"viewer:pw123456").decode())
        viewer = f"Bearer {body['token']}"
        st, _ = req("POST", "/api/instance/cluster/membership",
                    {"peers": peers3}, auth=viewer)
        assert st == 403

        # host 1 applies directly; host 0 over REST
        insts[1].apply_membership_change(peers3)
        st, body = req("POST", "/api/instance/cluster/membership",
                       {"peers": peers3}, auth=admin)
        assert st == 200, body
        moving = [t for t in toks if owning_process(t, 3) == 2]
        assert body["planned"] == len(moving)
        assert body["failed"] == 0
        for t in moving:
            assert third.device_management.get_device(t) is not None
    finally:
        web.stop()
        for inst in insts + ([third] if third else []):
            inst.stop()
            inst.terminate()


def test_forwarder_memory_mode_requeue(tmp_path):
    """apply_membership in memory-only mode (no data_dir): buffered rows
    for a departed peer re-route under the new map instead of waiting
    forever or dead-lettering."""
    from sitewhere_tpu.rpc.forward import HostForwarder

    class FakeDispatcher:
        def __init__(self):
            self.lines = []

        def ingest_wire_lines(self, payload, source_id="x",
                              raise_on_decode_error=False):
            lines = [l for l in payload.split(b"\n") if l.strip()]
            self.lines.extend(lines)
            return len(lines)

    disp = FakeDispatcher()
    # P=3, this host is 0; peers 1 and 2 have no demux (None) so their
    # rows just buffer (memory mode, never flushed during the test)
    fwd = HostForwarder(disp, process_id=0,
                        peer_demuxes={0: None, 1: None, 2: None},
                        deadline_ms=60_000.0)
    toks = {p: tokens_owned_by(p, 3, count=4) for p in range(3)}
    lines = [json.dumps({"deviceToken": t, "type": "Measurement",
                         "request": {"name": "x", "value": 1,
                                     "eventDate": 1}}).encode()
             for p in range(3) for t in toks[p]]
    fwd.ingest_payload(b"\n".join(lines))
    assert len(disp.lines) == 4              # only host-0 rows local
    assert sum(len(v) for v in fwd._buffers.values()) == 8

    # membership shrinks to [0, 1]: peer 2's buffered rows re-split —
    # each becomes local or peer-1-owned under the NEW 2-way map
    requeued = fwd.apply_membership({0: None, 1: None}, process_id=0)
    assert requeued == 8
    expect_local = [t for p in (1, 2) for t in toks[p]
                    if owning_process(t, 2) == 0]
    got_tokens = sorted(json.loads(l)["deviceToken"]
                        for l in disp.lines[4:])
    assert got_tokens == sorted(expect_local)
    # the rest sit buffered for peer 1 under the new map
    buffered = sum(len(v) for v in fwd._buffers.values())
    assert buffered == 8 - len(expect_local)
    assert fwd.dead_lettered == 0

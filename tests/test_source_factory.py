"""Config-driven event-source wiring (EventSourcesParser analog).

Reference: ``service-event-sources/.../spring/EventSourcesParser.java:27-50``
materializes receivers + decoder + deduplicator per source from tenant
config; here the same declaration is the instance config's ``sources``
list, built at start and attached through ``Instance.add_source``.
"""

import json
import socket
import struct

import pytest

from sitewhere_tpu.ingest.factory import build_sources
from sitewhere_tpu.instance import Instance
from sitewhere_tpu.runtime.config import Config
from sitewhere_tpu.services.common import ValidationError

from time_limit import wait_until


def test_factory_rejects_bad_declarations():
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "receivers": [{"type": "carrier-pigeon"}]}])
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "receivers": []}])
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "decoder": "nope",
                        "receivers": [{"type": "udp"}]}])
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "receivers": [
            {"type": "tcp", "framing": "morse"}]}])
    with pytest.raises(ValidationError):
        build_sources(["not-an-object"])


def test_factory_builds_each_receiver_type():
    srcs = build_sources([
        {"id": "a", "decoder": "jsonlines", "dedup": {"window": 128},
         "receivers": [
             {"type": "tcp", "framing": "newline"},
             {"type": "udp"},
             {"type": "http", "path": "/in"},
             {"type": "coap"},
             {"type": "stomp", "host": "broker.example", "port": 61613},
             {"type": "ws", "host": "feed.example", "port": 80},
             {"type": "poll", "url": "http://x/events", "interval_s": 60},
         ]},
    ])
    assert len(srcs) == 1
    assert len(srcs[0].receivers) == 7
    assert srcs[0].deduplicator is not None


def test_instance_boots_config_sources_and_ingests(tmp_path):
    cfg = Config({
        "instance": {"id": "cfg-src", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 256, "mtype_slots": 4,
                     "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "sources": [
            {"id": "wire", "decoder": "json",
             "receivers": [{"type": "tcp", "port": 0}]},
        ],
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    try:
        dm = inst.device_management
        dm.create_device_type(token="s", name="S")
        dm.create_device(token="d-1", device_type="s")
        dm.create_device_assignment(device="d-1")

        src = inst.sources[0]
        assert src.source_id == "wire"
        rx = src.receivers[0]
        payload = json.dumps({
            "deviceToken": "d-1", "type": "Measurement",
            "request": {"name": "t", "value": 7.5,
                        "eventDate": 1_753_800_000},
        }).encode()
        with socket.create_connection(("127.0.0.1", rx.port), timeout=5) as s:
            s.sendall(struct.pack(">I", len(payload)) + payload)
        assert wait_until(lambda: src.decoded_count >= 1)

        # decoded_count can tick before the row lands in the batcher
        # (the source thread is mid-forward), so a single flush may run
        # too early under load — flush-and-check until it lands
        def settled():
            inst.dispatcher.flush()
            return inst.event_store.total_events == 1

        assert wait_until(settled)
    finally:
        inst.stop()
        inst.terminate()


def test_instance_bad_source_config_fails_boot(tmp_path):
    cfg = Config({
        "instance": {"id": "cfg-bad", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 256, "mtype_slots": 4,
                     "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "sources": [{"id": "x", "receivers": [{"type": "smoke-signal"}]}],
    }, apply_env=False)
    inst = Instance(cfg)
    with pytest.raises(ValidationError):
        inst.start()
    inst.terminate()


def test_factory_validation_gaps_closed():
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "receivers": ["tcp"]}])  # non-dict rx
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "dedup": True,
                        "receivers": [{"type": "udp"}]}])
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "dedup": {"windw": 1},
                        "receivers": [{"type": "udp"}]}])


def test_factory_rejects_non_decoder_script(tmp_path):
    from sitewhere_tpu.runtime.scripting import ScriptManager

    scripts = ScriptManager(str(tmp_path))
    scripts.upload("norm", "processor",
                   "def process(cols, mask):\n    return None\n")
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "decoder": "norm",
                        "receivers": [{"type": "udp"}]}], scripts=scripts)


def test_raw_wire_source_takes_columnar_lane(tmp_path):
    """A `"raw_wire": true` source hands NDJSON payloads straight to
    dispatcher.ingest_wire_lines (C columnar decode + in-scanner token
    resolution): events land, registration lines in the payload still
    route to the host plane, and a bad payload dead-letters without
    killing the receiver."""
    cfg = Config({
        "instance": {"id": "raw-src", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 256, "mtype_slots": 4,
                     "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "registration": {"default_device_type": "s"},
        "sources": [
            {"id": "raw", "decoder": "jsonlines", "raw_wire": True,
             "receivers": [{"type": "tcp", "port": 0}]},
        ],
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    try:
        dm = inst.device_management
        dm.create_device_type(token="s", name="S")
        for i in range(4):
            dm.create_device(token=f"d-{i}", device_type="s")
            dm.create_device_assignment(device=f"d-{i}")

        src = inst.sources[0]
        assert src.raw_wire and src.on_wire_payload is not None
        rx = src.receivers[0]
        lines = [json.dumps({
            "deviceToken": f"d-{i % 4}", "type": "Measurement",
            "request": {"name": "t", "value": float(i),
                        "eventDate": 1_753_800_000 + i}})
            for i in range(16)]
        # a registration line mid-payload must route to the host plane
        lines.append(json.dumps({
            "deviceToken": "d-new", "type": "RegisterDevice",
            "request": {"deviceTypeToken": "s"}}))
        payload = "\n".join(lines).encode()
        with socket.create_connection(("127.0.0.1", rx.port), timeout=5) as s:
            s.sendall(struct.pack(">I", len(payload)) + payload)
        assert wait_until(lambda: src.decoded_count >= 16)

        def settled():
            inst.dispatcher.flush()
            return (inst.event_store.total_events == 16
                    and "d-new" in inst.identity.device)

        assert wait_until(settled)

        # an undecodable payload dead-letters whole; the receiver lives
        before = inst.dispatcher.dead_letters.end_offset
        with socket.create_connection(("127.0.0.1", rx.port), timeout=5) as s:
            bad = b'{"not": "wire'
            s.sendall(struct.pack(">I", len(bad)) + bad)
        assert wait_until(
            lambda: inst.dispatcher.dead_letters.end_offset > before)
        assert src.failed_count == 1  # raw-lane failures tick the source
        with socket.create_connection(("127.0.0.1", rx.port), timeout=5) as s:
            good = lines[0].encode()
            s.sendall(struct.pack(">I", len(good)) + good)

        def one_more():
            inst.dispatcher.flush()
            return inst.event_store.total_events == 17

        assert wait_until(one_more)
    finally:
        inst.stop()
        inst.terminate()


def test_raw_wire_rejects_dedup():
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "raw_wire": True,
                        "dedup": {"window": 64},
                        "receivers": [{"type": "udp"}]}])


def test_raw_wire_rejects_non_json_decoder():
    # the raw lane never runs the configured decoder; a binary decoder
    # paired with it must fail boot, not silently dead-letter at runtime
    with pytest.raises(ValidationError):
        build_sources([{"id": "x", "raw_wire": True, "decoder": "binary",
                        "receivers": [{"type": "udp"}]}])


def test_raw_wire_source_owner_splits_in_multihost(tmp_path):
    """With a forwarder (rpc.peers), a raw_wire source's payloads go
    through ingest_payload: locally-owned lines take the columnar lane
    in-process, remote-owned lines ship to their owning host."""
    from sitewhere_tpu.rpc.forward import owning_process

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    ports = [free_port(), free_port()]
    peers = [f"127.0.0.1:{p}" for p in ports]
    insts = []
    for p in range(2):
        cfg = Config({
            "instance": {"id": "raw-mh",
                         "data_dir": str(tmp_path / f"host{p}" / "data")},
            "pipeline": {"width": 64, "registry_capacity": 1024,
                         "mtype_slots": 4, "deadline_ms": 5.0,
                         "n_shards": 1},
            "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
            # the rows' path, not admission: a loaded machine's pressure
            # must not shed them on the way
            "overload": {"enabled": False},
            "rpc": {"server": {"enabled": True, "host": "127.0.0.1",
                               "port": ports[p]},
                    "process_id": p, "peers": peers,
                    "forward_deadline_ms": 10.0},
            "security": {"jwt_secret": "shared-test-secret"},
            **({"sources": [{"id": "raw", "decoder": "jsonlines",
                             "raw_wire": True,
                             "receivers": [{"type": "tcp", "port": 0}]}]}
               if p == 0 else {}),
        }, apply_env=False)
        inst = Instance(cfg)
        inst.start()
        inst.device_management.create_device_type(token="sensor", name="S")
        insts.append(inst)
    try:
        src = insts[0].sources[0]
        assert src.raw_wire and src.on_wire_payload is not None
        tok0 = next(f"dev-{i}" for i in range(100)
                    if owning_process(f"dev-{i}", 2) == 0)
        tok1 = next(f"dev-{i}" for i in range(100)
                    if owning_process(f"dev-{i}", 2) == 1)
        for inst, tok in ((insts[0], tok0), (insts[1], tok1)):
            inst.device_management.create_device(token=tok,
                                                 device_type="sensor")
            inst.device_management.create_device_assignment(device=tok)

        payload = "\n".join(json.dumps({
            "deviceToken": tok, "type": "Measurement",
            "request": {"name": "t", "value": v, "eventDate": 1000}})
            for tok, v in ((tok0, 1.0), (tok1, 2.0),
                           (tok0, 3.0), (tok1, 4.0))).encode()
        port = src.receivers[0].port
        with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
            s.sendall(struct.pack(">I", len(payload)) + payload)

        def both_landed():
            insts[0].forwarder.flush(wait=True)
            for inst in insts:
                inst.dispatcher.flush()
            d0 = int(insts[0].identity.device.lookup(tok0))
            d1 = int(insts[1].identity.device.lookup(tok1))
            return (len(insts[0].event_store.query(device_id=d0)) == 2
                    and len(insts[1].event_store.query(device_id=d1)) == 2)

        assert wait_until(both_landed)
        assert src.decoded_count == 2  # the locally-accepted rows
    finally:
        for inst in insts:
            inst.stop()
            inst.terminate()

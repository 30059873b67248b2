"""Protocol frontends: drive real sockets end-to-end into decoded requests."""

import json
import socket
import struct
import threading
import urllib.request

import pytest

from sitewhere_tpu.ingest.decoders import JsonDecoder, RequestKind
from sitewhere_tpu.ingest.dedup import AlternateIdDeduplicator
from sitewhere_tpu.ingest.sources import (
    HttpReceiver,
    InboundEventSource,
    TcpReceiver,
    UdpReceiver,
    newline_frames,
)

from time_limit import wait_until


def meas_payload(token="dev-1", value=1.0, alt=None):
    req = {"name": "temp", "value": value, "eventDate": 1000}
    if alt:
        req["alternateId"] = alt
    return json.dumps({"deviceToken": token, "type": "Measurement",
                       "request": req}).encode()


def make_source(receivers, dedup=None):
    events, regs, failures = [], [], []
    src = InboundEventSource(
        "test", receivers, JsonDecoder(), deduplicator=dedup,
        on_event=lambda req, raw: events.append(req),
        on_registration=lambda req, raw: regs.append(req),
        on_failed_decode=lambda raw, sid, e: failures.append((raw, str(e))),
    )
    return src, events, regs, failures


def test_tcp_receiver_length_prefixed():
    src, events, _, failures = make_source([TcpReceiver(port=0)])
    src.start()
    try:
        port = src.receivers[0].port
        with socket.create_connection(("127.0.0.1", port)) as s:
            for v in (1.0, 2.0):
                payload = meas_payload(value=v)
                s.sendall(struct.pack(">I", len(payload)) + payload)
            bad = b"this is not json"
            s.sendall(struct.pack(">I", len(bad)) + bad)
        assert wait_until(lambda: len(events) == 2 and len(failures) == 1)
        assert [e.value for e in events] == [1.0, 2.0]
        assert events[0].kind == RequestKind.MEASUREMENT
    finally:
        src.stop()


def test_tcp_receiver_newline_framing():
    src, events, _, _ = make_source(
        [TcpReceiver(port=0, framing=newline_frames)]
    )
    src.start()
    try:
        port = src.receivers[0].port
        with socket.create_connection(("127.0.0.1", port)) as s:
            s.sendall(meas_payload(value=5.0) + b"\n" + meas_payload(value=6.0) + b"\n")
        assert wait_until(lambda: len(events) == 2)
        assert {e.value for e in events} == {5.0, 6.0}
    finally:
        src.stop()


def test_udp_receiver():
    src, events, _, _ = make_source([UdpReceiver(port=0)])
    src.start()
    try:
        port = src.receivers[0].port
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.sendto(meas_payload(value=9.0), ("127.0.0.1", port))
        assert wait_until(lambda: len(events) == 1)
        assert events[0].value == 9.0
    finally:
        src.stop()


def test_http_receiver_and_registration_routing():
    src, events, regs, _ = make_source([HttpReceiver(port=0)])
    src.start()
    try:
        port = src.receivers[0].port
        url = f"http://127.0.0.1:{port}/events"
        reg = json.dumps({"deviceToken": "new-dev", "type": "RegisterDevice",
                          "request": {"deviceTypeToken": "pi"}}).encode()
        for body in (meas_payload(), reg):
            r = urllib.request.urlopen(urllib.request.Request(
                url, data=body, method="POST"))
            assert r.status == 202
        assert wait_until(lambda: len(events) == 1 and len(regs) == 1)
        assert regs[0].device_type_token == "pi"
        # wrong path -> 404, no event
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/nope", data=b"x", method="POST"))
    finally:
        src.stop()


def test_source_dedups_across_receivers():
    dedup = AlternateIdDeduplicator()
    src, events, _, _ = make_source([UdpReceiver(port=0)], dedup=dedup)
    src.start()
    try:
        port = src.receivers[0].port
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for _ in range(3):
            s.sendto(meas_payload(alt="same-msg"), ("127.0.0.1", port))
        assert wait_until(lambda: src.receivers[0].received_count == 3)
        assert wait_until(lambda: len(events) == 1)
        assert src.duplicate_count == 2
    finally:
        src.stop()


def test_lifecycle_status_tree():
    src, *_ = make_source([UdpReceiver(port=0), HttpReceiver(port=0)])
    src.start()
    try:
        tree = src.status_tree()
        assert tree["state"] == "started"
        assert len(tree["children"]) == 2
        assert all(c["state"] == "started" for c in tree["children"])
    finally:
        src.stop()
    assert src.status_tree()["state"] == "stopped"


def test_host_plane_request_does_not_kill_receiver():
    src, events, _, _ = make_source([UdpReceiver(port=0)])
    # wire on_host_request absent: stream data should be counted, dropped
    src.start()
    try:
        port = src.receivers[0].port
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.sendto(json.dumps({"deviceToken": "d", "type": "StreamData",
                             "request": {"streamId": "s1",
                                         "sequenceNumber": 0,
                                         "data": "AAAA"}}).encode(),
                 ("127.0.0.1", port))
        # malformed stream request (no streamId) dead-letters as a
        # failed decode rather than killing the receiver
        s.sendto(json.dumps({"deviceToken": "d", "type": "StreamData",
                             "request": {}}).encode(), ("127.0.0.1", port))
        s.sendto(meas_payload(value=3.0), ("127.0.0.1", port))
        assert wait_until(lambda: len(events) == 1)  # receiver survived
        assert src.dropped_host_requests == 1
        assert src.failed_count == 1
    finally:
        src.stop()


def test_broken_sink_does_not_kill_receiver():
    def exploding_sink(req, raw):
        raise RuntimeError("sink bug")

    src = InboundEventSource("t", [UdpReceiver(port=0)], JsonDecoder(),
                             on_event=exploding_sink)
    src.start()
    try:
        port = src.receivers[0].port
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.sendto(meas_payload(value=1.0), ("127.0.0.1", port))
        s.sendto(meas_payload(value=2.0), ("127.0.0.1", port))
        assert wait_until(lambda: src.failed_count == 2)  # both logged, thread alive
        assert src.receivers[0].received_count == 2
    finally:
        src.stop()

"""STOMP receiver (ActiveMQ/RabbitMQ analog), HTTP webhook connector
(InitialState/dweet analog), and HTTP SMS gateway delivery (Twilio analog).

Reference files these mirror:
``service-event-sources/.../activemq/ActiveMQClientEventReceiver.java``,
``.../rabbitmq/RabbitMqInboundEventReceiver.java``,
``service-outbound-connectors/.../initialstate``/``dweetio``,
``service-command-delivery/.../twilio/TwilioCommandDeliveryProvider.java``.
"""

import http.server
import json
import socket
import socketserver
import threading
import time

import numpy as np
import pytest

from sitewhere_tpu.commands.destinations import (
    DeliveryError,
    HttpDeliveryProvider,
    SmsParameterExtractor,
)
from sitewhere_tpu.commands.model import CommandExecution, CommandInvocation
from sitewhere_tpu.ingest.stomp import (
    FrameReader,
    StompError,
    StompReceiver,
    encode_frame,
)
from sitewhere_tpu.netio import close_socket
from sitewhere_tpu.outbound.connectors import HttpConnector

from time_limit import wait_until


# ---------------------------------------------------------------------------
# frame codec
# ---------------------------------------------------------------------------

def test_frame_roundtrip_with_escapes_and_binary_body():
    body = b"\x00\x01binary\nbody\x00"
    raw = encode_frame("SEND", {"destination": "/queue/a:b\nc"}, body)
    frames = FrameReader().feed(raw)
    assert len(frames) == 1
    command, headers, got = frames[0]
    assert command == "SEND"
    assert headers["destination"] == "/queue/a:b\nc"
    assert got == body
    assert headers["content-length"] == str(len(body))


def test_reader_handles_heartbeats_split_frames_and_crlf():
    r = FrameReader()
    raw = b"\n\n" + encode_frame("MESSAGE", {"ack": "m1"}, b"hello")
    # feed one byte at a time: the parser must buffer partial frames
    frames = []
    for i in range(len(raw)):
        frames += r.feed(raw[i:i + 1])
    assert [f[0] for f in frames] == ["MESSAGE"]
    assert frames[0][2] == b"hello"
    # CRLF head form
    crlf = b"MESSAGE\r\nack:m2\r\n\r\nworld\x00"
    (cmd, headers, body), = r.feed(crlf)
    assert (cmd, headers["ack"], body) == ("MESSAGE", "m2", b"world")


def test_reader_first_header_occurrence_wins_and_bad_escape_raises():
    (_, headers, _), = FrameReader().feed(
        b"MESSAGE\nfoo:one\nfoo:two\n\n\x00")
    assert headers["foo"] == "one"
    with pytest.raises(StompError):
        FrameReader().feed(b"MESSAGE\nbad:\\x\n\n\x00")


# ---------------------------------------------------------------------------
# mini broker: scripted STOMP server for end-to-end receiver tests
# ---------------------------------------------------------------------------

class MiniBroker:
    """Single-session scripted broker: CONNECT→CONNECTED, records
    SUBSCRIBE/ACK frames, pushes queued MESSAGEs."""

    def __init__(self, drop_first_session=False, heartbeat="0,0",
                 raw_capture=None, go_silent_after_subscribe=False):
        self.acks = []
        self.subscribes = []
        self.sessions = 0
        self.drop_first_session = drop_first_session
        self.heartbeat = heartbeat
        self.raw_capture = raw_capture
        self.go_silent_after_subscribe = go_silent_after_subscribe
        self._to_send = []
        self._lock = threading.Lock()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(4)
        self.port = self._srv.getsockname()[1]
        self._alive = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def push(self, ack_id, body):
        with self._lock:
            self._to_send.append((ack_id, body))

    def close(self):
        self._alive = False
        close_socket(self._srv)   # wakes the blocked accept

    def _loop(self):
        while self._alive:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            self.sessions += 1
            if self.drop_first_session and self.sessions == 1:
                conn.close()  # force the receiver's reconnect path
                continue
            threading.Thread(
                target=self._session, args=(conn,), daemon=True).start()

    def _session(self, conn):
        reader = FrameReader()
        conn.settimeout(0.05)
        subscribed = False
        try:
            while self._alive:
                if subscribed:  # a real broker never delivers pre-SUBSCRIBE
                    if self.go_silent_after_subscribe:
                        # stop answering entirely (still RECORDING what
                        # the client sends): the client's dead-connection
                        # cutoff must fire
                        try:
                            got = conn.recv(65536)
                            if not got:
                                return  # client cut the connection
                            if self.raw_capture is not None:
                                self.raw_capture.append(got)
                        except socket.timeout:
                            pass
                        continue
                    with self._lock:
                        pending, self._to_send = self._to_send, []
                    for ack_id, body in pending:
                        conn.sendall(encode_frame("MESSAGE", {
                            "destination": "/queue/q", "message-id": ack_id,
                            "subscription": "0", "ack": ack_id,
                        }, body))
                try:
                    data = conn.recv(65536)
                except socket.timeout:
                    continue
                if not data:
                    return
                if self.raw_capture is not None:
                    self.raw_capture.append(data)
                for cmd, headers, _ in reader.feed(data):
                    if cmd == "CONNECT":
                        conn.sendall(encode_frame(
                            "CONNECTED",
                            {"version": "1.2",
                             "heart-beat": self.heartbeat},
                            escape=False))
                    elif cmd == "SUBSCRIBE":
                        subscribed = True
                        self.subscribes.append(headers)
                    elif cmd == "ACK":
                        self.acks.append(headers["id"])
        except OSError:
            pass
        finally:
            conn.close()


def test_stomp_receiver_subscribes_delivers_and_acks():
    broker = MiniBroker()
    got = []
    rx = StompReceiver("127.0.0.1", broker.port, destination="/queue/q",
                       heartbeat_ms=0)
    rx.sink = got.append
    rx.start()
    try:
        assert wait_until(lambda: broker.subscribes)
        assert broker.subscribes[0]["destination"] == "/queue/q"
        assert broker.subscribes[0]["ack"] == "client-individual"
        broker.push("m-1", b'{"device":"d-1"}')
        broker.push("m-2", b'{"device":"d-2"}')
        assert wait_until(lambda: len(got) == 2)
        assert got == [b'{"device":"d-1"}', b'{"device":"d-2"}']
        # per-message acks arrive only after the sink accepted the payload
        assert wait_until(lambda: broker.acks == ["m-1", "m-2"])
    finally:
        rx.stop()
        broker.close()


def test_stomp_receiver_reconnects_after_dropped_session():
    broker = MiniBroker(drop_first_session=True)
    got = []
    rx = StompReceiver("127.0.0.1", broker.port, destination="/queue/q",
                       heartbeat_ms=0, reconnect_delay_s=0.05)
    rx.sink = got.append
    rx.start()
    try:
        assert wait_until(lambda: broker.subscribes)  # second session made it
        assert broker.sessions >= 2
        broker.push("m-9", b"payload")
        assert wait_until(lambda: got == [b"payload"])
    finally:
        rx.stop()
        broker.close()


# ---------------------------------------------------------------------------
# HTTP webhook connector + SMS gateway provider
# ---------------------------------------------------------------------------

class _CaptureHandler(http.server.BaseHTTPRequestHandler):
    status = 200

    def do_POST(self):
        length = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(length)
        self.server.requests.append(
            (self.path, dict(self.headers), body))
        self.send_response(self.server.status)
        self.end_headers()

    def log_message(self, *args):
        pass


def _http_server(status=200):
    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), _CaptureHandler)
    srv.requests = []
    srv.status = status
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _cols(n):
    return {
        "event_type": np.zeros(n, np.int32),
        "device_id": np.arange(n, dtype=np.int32),
        "tenant_id": np.zeros(n, np.int32),
        "ts_s": np.full(n, 1_753_800_000, np.int32),
        "ts_ns": np.zeros(n, np.int32),
        "mtype_id": np.zeros(n, np.int32),
        "value": np.linspace(1.0, 2.0, n).astype(np.float32),
    }


def test_http_connector_posts_surviving_rows_as_json_array():
    srv = _http_server()
    try:
        c = HttpConnector(
            "webhook", f"http://127.0.0.1:{srv.server_address[1]}/hook",
            headers={"X-Api-Key": "k1"})
        mask = np.array([True, False, True])
        assert c.process_batch(_cols(3), mask) == 2
        assert len(srv.requests) == 1
        path, headers, body = srv.requests[0]
        assert path == "/hook"
        assert headers["X-Api-Key"] == "k1"
        docs = json.loads(body)
        assert [d["deviceId"] for d in docs] == [0, 2]
        # keep-alive: second batch reuses the connection
        assert c.process_batch(_cols(3), mask) == 2
        assert len(srv.requests) == 2
        assert c.errors == 0
    finally:
        srv.shutdown()
        srv.server_close()


def test_http_connector_counts_rejections():
    srv = _http_server(status=503)
    try:
        c = HttpConnector(
            "webhook", f"http://127.0.0.1:{srv.server_address[1]}/hook")
        # a rejected POST now RAISES (so an attached breaker sees the
        # failing sink; the manager isolates it) and is counted once
        from sitewhere_tpu.outbound.connectors import DeliveryFailed

        with pytest.raises(DeliveryFailed):
            c.process_batch(_cols(2), np.array([True, True]))
        assert c.errors == 1
    finally:
        srv.shutdown()
        srv.server_close()


def _execution(metadata):
    inv = CommandInvocation(
        command_token="reboot", target_assignment="a-1",
        device_token="d-1", tenant="t0")
    return CommandExecution(
        invocation=inv, command_name="reboot", namespace="sw",
        device_metadata=metadata)


def test_http_sms_gateway_delivery_and_missing_phone_dead_letters():
    srv = _http_server()
    try:
        provider = HttpDeliveryProvider(
            f"http://127.0.0.1:{srv.server_address[1]}/2010-04-01/Messages",
            field_map={"To": "{phone}", "From": "+15550100",
                       "Body": "{payload}"})
        extractor = SmsParameterExtractor()
        ex = _execution({"phone_number": "+15550123"})
        provider.deliver(ex, b"reboot now", extractor(ex))
        path, headers, body = srv.requests[0]
        assert path == "/2010-04-01/Messages"
        fields = dict(p.split("=", 1) for p in body.decode().split("&"))
        assert fields["To"] == "%2B15550123"
        assert fields["Body"] == "reboot+now"
        # device without a phone number → DeliveryError → undelivered
        ex2 = _execution({})
        with pytest.raises(DeliveryError):
            provider.deliver(ex2, b"x", extractor(ex2))
    finally:
        srv.shutdown()
        srv.server_close()


def test_http_sms_gateway_error_status_raises():
    srv = _http_server(status=401)
    try:
        provider = HttpDeliveryProvider(
            f"http://127.0.0.1:{srv.server_address[1]}/msg")
        extractor = SmsParameterExtractor()
        ex = _execution({"phone_number": "+15550123"})
        with pytest.raises(DeliveryError):
            provider.deliver(ex, b"x", extractor(ex))
    finally:
        srv.shutdown()
        srv.server_close()


def test_stomp_poison_message_left_unacked_and_receiver_survives():
    broker = MiniBroker()
    got = []

    def sink(payload):
        if payload == b"poison":
            raise ValueError("bad payload")
        got.append(payload)

    rx = StompReceiver("127.0.0.1", broker.port, destination="/queue/q",
                       heartbeat_ms=0)
    rx.sink = sink
    rx.start()
    try:
        assert wait_until(lambda: broker.subscribes)
        broker.push("m-1", b"poison")
        broker.push("m-2", b"fine")
        assert wait_until(lambda: got == [b"fine"])
        assert wait_until(lambda: broker.acks == ["m-2"])  # poison NOT acked
        assert rx.emit_errors == 1
    finally:
        rx.stop()
        broker.close()


def test_stomp_heartbeats_negotiated_and_sent():
    """CONNECTED advertising heart-beats makes the client emit LF frames
    on the negotiated cadence and CUT a connection to a silent broker
    (then retry after the reconnect backoff)."""
    raw_frames = []
    broker = MiniBroker(heartbeat="100,100", raw_capture=raw_frames,
                        go_silent_after_subscribe=True)
    rx = StompReceiver("127.0.0.1", broker.port, destination="/queue/q",
                       heartbeat_ms=100, reconnect_delay_s=0.2)
    rx.sink = lambda p: None
    rx.start()
    try:
        assert wait_until(lambda: broker.subscribes)
        # client LF heart-beats arrive (boundary-insensitive: a chunk of
        # nothing but LFs, however many coalesced)
        assert wait_until(lambda: any(
            d and d.strip(b"\n") == b"" for d in raw_frames), timeout=2.0)
        # silent broker -> heart-beat cutoff -> reconnect attempt: the
        # broker sees a SECOND session (would never happen if the
        # dead-connection detection in _session were removed)
        assert wait_until(lambda: broker.sessions >= 2)
    finally:
        rx.stop()
        broker.close()


# ---------------------------------------------------------------------------
# index-push connector (SolrOutboundConnector analog)
# ---------------------------------------------------------------------------

def test_index_push_accumulates_and_flushes_bulk():
    """Events accumulate across pipeline batches and flush as ONE bulk
    request at the row threshold."""
    from sitewhere_tpu.outbound import IndexPushConnector

    srv = _http_server()
    try:
        c = IndexPushConnector(
            "solr", f"http://127.0.0.1:{srv.server_address[1]}/update",
            bulk_rows=5, bulk_interval_s=3600.0)
        # 3 rows: below threshold — nothing posted yet
        c.process_batch(_cols(3), np.ones(3, np.bool_))
        assert len(srv.requests) == 0
        # 3 more: threshold crossed — one bulk of all 6
        c.process_batch(_cols(3), np.ones(3, np.bool_))
        assert len(srv.requests) == 1
        docs = json.loads(srv.requests[0][2])
        assert len(docs) == 6
        assert c.indexed == 6 and c.errors == 0
        c.stop()
    finally:
        srv.shutdown()
        srv.server_close()


def test_index_push_interval_flush_and_final_flush_on_stop():
    from sitewhere_tpu.outbound import IndexPushConnector

    srv = _http_server()
    try:
        c = IndexPushConnector(
            "solr", f"http://127.0.0.1:{srv.server_address[1]}/update",
            bulk_rows=1000, bulk_interval_s=0.1)
        c.start()
        c.process_batch(_cols(2), np.ones(2, np.bool_))
        assert wait_until(lambda: srv.requests)
        assert len(srv.requests) == 1  # interval flush
        c.process_batch(_cols(1), np.ones(1, np.bool_))
        c.stop()  # final best-effort flush
        assert sum(len(json.loads(b)) for _, _, b in srv.requests) == 3
    finally:
        srv.shutdown()
        srv.server_close()


def test_index_push_retries_with_backoff_without_loss():
    """A failed bulk is retained and re-sent once the sink recovers."""
    from sitewhere_tpu.outbound import IndexPushConnector

    srv = _http_server(status=500)
    try:
        c = IndexPushConnector(
            "solr", f"http://127.0.0.1:{srv.server_address[1]}/update",
            bulk_rows=2, bulk_interval_s=3600.0, backoff_s=0.05)
        c.process_batch(_cols(2), np.ones(2, np.bool_))
        assert c.errors == 1 and c.indexed == 0
        assert len(c._pending) == 2  # retained for retry
        srv.status = 200
        time.sleep(0.06)  # let the backoff window pass
        c.process_batch(_cols(1), np.ones(1, np.bool_))
        assert c.indexed == 3
        assert len(c._pending) == 0
        # everything arrived exactly once after recovery
        ok = [b for _, _, b in srv.requests if len(json.loads(b)) == 3]
        assert len(ok) == 1
        c.stop()
    finally:
        srv.shutdown()
        srv.server_close()


def test_index_push_bounded_buffer_drops_oldest():
    from sitewhere_tpu.outbound import IndexPushConnector

    srv = _http_server(status=500)
    try:
        c = IndexPushConnector(
            "solr", f"http://127.0.0.1:{srv.server_address[1]}/update",
            bulk_rows=100, bulk_interval_s=3600.0, max_buffer_rows=4,
            backoff_s=3600.0)
        c.process_batch(_cols(3), np.ones(3, np.bool_))
        c.process_batch(_cols(3), np.ones(3, np.bool_))
        assert c.dropped == 2
        assert len(c._pending) == 4
        # the RETAINED docs are the newest ones
        vals = [d["deviceId"] for d in c._pending]
        assert vals == [2, 0, 1, 2]
        c.stop()
    finally:
        srv.shutdown()
        srv.server_close()


def test_index_push_custom_bulk_format():
    """An Elasticsearch-style _bulk NDJSON builder plugs in unchanged."""
    from sitewhere_tpu.outbound import IndexPushConnector

    def es_bulk(docs):
        lines = []
        for d in docs:
            lines.append(json.dumps({"index": {"_index": "events"}}))
            lines.append(json.dumps(d))
        return ("\n".join(lines) + "\n").encode()

    srv = _http_server()
    try:
        c = IndexPushConnector(
            "es", f"http://127.0.0.1:{srv.server_address[1]}/_bulk",
            bulk_rows=2, bulk_interval_s=3600.0, bulk_format=es_bulk)
        c.process_batch(_cols(2), np.ones(2, np.bool_))
        assert len(srv.requests) == 1
        body = srv.requests[0][2].decode().strip().split("\n")
        assert len(body) == 4  # action+doc per event
        assert json.loads(body[0]) == {"index": {"_index": "events"}}
        assert json.loads(body[1])["deviceId"] == 0
        c.stop()
    finally:
        srv.shutdown()
        srv.server_close()

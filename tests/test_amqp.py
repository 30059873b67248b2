"""AMQP 0-9-1 receiver against a scripted mini-broker.

Reference behavior covered: ``RabbitMqInboundEventReceiver.java`` —
consume a queue over the broker's native protocol with explicit acks
(at-least-once), reconnect on session loss.
"""

import socket
import struct
import threading
import time

import pytest

from sitewhere_tpu.ingest.amqp import (
    BASIC_ACK,
    BASIC_NACK,
    BASIC_CONSUME,
    BASIC_CONSUME_OK,
    BASIC_DELIVER,
    BASIC_QOS,
    BASIC_QOS_OK,
    CHANNEL_OPEN,
    CHANNEL_OPEN_OK,
    CONNECTION_OPEN,
    CONNECTION_OPEN_OK,
    CONNECTION_START,
    CONNECTION_START_OK,
    CONNECTION_TUNE,
    CONNECTION_TUNE_OK,
    FRAME_BODY,
    FRAME_HEADER,
    FRAME_METHOD,
    PROTOCOL_HEADER,
    QUEUE_DECLARE,
    QUEUE_DECLARE_OK,
    AmqpError,
    AmqpReceiver,
    FrameReader,
    field_table,
    frame,
    longstr,
    method_frame,
    parse_shortstr,
    shortstr,
)
from sitewhere_tpu.netio import close_socket

from time_limit import wait_until


class MiniAmqpBroker:
    """Single-queue scripted broker: full consume handshake, records
    declares/acks/auth, pushes queued deliveries (optionally split
    across several body frames)."""

    def __init__(self, heartbeat=0, body_frame_size=0,
                 drop_first_session=False, coalesce_first_delivery=False):
        self.heartbeat = heartbeat
        self.body_frame_size = body_frame_size
        self.drop_first_session = drop_first_session
        self.coalesce_first_delivery = coalesce_first_delivery
        self.acks = []
        self.nacks = []
        self.declares = []
        self.auth = None
        self.sessions = 0
        self._to_send = []
        self._lock = threading.Lock()
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(4)
        self.port = self._srv.getsockname()[1]
        self._alive = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def push(self, payload: bytes):
        with self._lock:
            self._to_send.append(payload)

    def close(self):
        self._alive = False
        close_socket(self._srv)   # wakes the blocked accept

    # -- server side ---------------------------------------------------------

    def _loop(self):
        while self._alive:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            self.sessions += 1
            if self.drop_first_session and self.sessions == 1:
                conn.close()
                continue
            try:
                self._session(conn)
            except (OSError, AmqpError):
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def _recv_method(self, conn, reader, want, pending):
        # frames coalesced into one recv AFTER the awaited method stay
        # on ``pending`` for the next call — returning mid-batch used to
        # DROP them (e.g. connection.open right behind tune-ok when the
        # client's sends coalesce in the kernel), wedging the handshake
        # until both sides timed out: the historical flake in this file
        while True:
            while pending:
                ftype, channel, payload = pending.pop(0)
                if ftype != FRAME_METHOD:
                    continue
                cm = struct.unpack_from(">HH", payload, 0)
                if cm == want:
                    return channel, payload[4:]
                if cm == BASIC_ACK:
                    tag = struct.unpack_from(">Q", payload, 4)[0]
                    self.acks.append(tag)
                    continue
                raise AmqpError(f"mini-broker: unexpected {cm}")
            data = conn.recv(65536)
            if not data:
                raise AmqpError("mini-broker: client gone")
            pending.extend(reader.feed(data))

    def _session(self, conn):
        conn.settimeout(10)
        reader = FrameReader()
        pending = []
        hdr = b""
        while len(hdr) < 8:
            hdr += conn.recv(8 - len(hdr))
        assert hdr == PROTOCOL_HEADER
        conn.sendall(method_frame(0, CONNECTION_START, struct.pack(
            ">BB", 0, 9) + field_table({}) + longstr(b"PLAIN")
            + longstr(b"en_US")))
        _, args = self._recv_method(conn, reader, CONNECTION_START_OK,
                                    pending)
        # client-properties table, then mechanism + response
        tbl_len = struct.unpack_from(">I", args, 0)[0]
        off = 4 + tbl_len
        mech, off = parse_shortstr(args, off)
        resp_len = struct.unpack_from(">I", args, off)[0]
        self.auth = (mech, args[off + 4: off + 4 + resp_len])
        conn.sendall(method_frame(0, CONNECTION_TUNE, struct.pack(
            ">HIH", 2047, 131072, self.heartbeat)))
        self._recv_method(conn, reader, CONNECTION_TUNE_OK, pending)
        self._recv_method(conn, reader, CONNECTION_OPEN, pending)
        conn.sendall(method_frame(0, CONNECTION_OPEN_OK, shortstr("")))
        ch, _ = self._recv_method(conn, reader, CHANNEL_OPEN, pending)
        conn.sendall(method_frame(ch, CHANNEL_OPEN_OK, struct.pack(">I", 0)))
        self._recv_method(conn, reader, BASIC_QOS, pending)
        conn.sendall(method_frame(ch, BASIC_QOS_OK))
        _, args = self._recv_method(conn, reader, QUEUE_DECLARE, pending)
        qname, _ = parse_shortstr(args, 2)
        self.declares.append(qname)
        conn.sendall(method_frame(ch, QUEUE_DECLARE_OK, shortstr(qname)
                                  + struct.pack(">II", 0, 0)))
        self._recv_method(conn, reader, BASIC_CONSUME, pending)
        tag = 0
        ok = method_frame(ch, BASIC_CONSUME_OK, shortstr("ctag-1"))
        if self.coalesce_first_delivery:
            # one TCP segment: consume-ok + every already-queued delivery
            # (what a real broker's socket can do under load)
            with self._lock:
                sendables = self._to_send[:]
                self._to_send.clear()
            for payload in sendables:
                tag += 1
                ok += self._delivery_frames(ch, tag, payload)
        conn.sendall(ok)

        # deliver queued payloads; keep reading acks.  Nacked-with-requeue
        # deliveries go back on the queue and REDELIVER immediately under
        # a fresh tag, like RabbitMQ does for a sole consumer.
        unacked = {}
        conn.settimeout(0.05)
        while self._alive:
            with self._lock:
                sendables = self._to_send[:]
                self._to_send.clear()
            for payload in sendables:
                tag += 1
                unacked[tag] = payload
                conn.sendall(self._delivery_frames(ch, tag, payload))
            frames = pending[:]
            pending.clear()
            if not frames:
                try:
                    data = conn.recv(65536)
                except socket.timeout:
                    continue
                if not data:
                    return
                frames = reader.feed(data)
            for ftype, _, payload in frames:
                if ftype == FRAME_METHOD:
                    cm = struct.unpack_from(">HH", payload, 0)
                    if cm == BASIC_ACK:
                        t = struct.unpack_from(">Q", payload, 4)[0]
                        self.acks.append(t)
                        unacked.pop(t, None)
                    elif cm == BASIC_NACK:
                        t, bits = struct.unpack_from(">QB", payload, 4)
                        self.nacks.append((t, bits))
                        body = unacked.pop(t, None)
                        if body is not None and bits & 0x02:
                            with self._lock:
                                self._to_send.append(body)

    def _delivery_frames(self, ch, tag, payload):
        out = method_frame(ch, BASIC_DELIVER,
                           shortstr("ctag-1") + struct.pack(">QB", tag, 0)
                           + shortstr("") + shortstr("rk"))
        out += frame(FRAME_HEADER, ch, struct.pack(
            ">HHQH", 60, 0, len(payload), 0))
        step = self.body_frame_size or len(payload) or 1
        for lo in range(0, len(payload), step):
            out += frame(FRAME_BODY, ch, payload[lo: lo + step])
        if not payload:
            out += frame(FRAME_BODY, ch, b"")
        return out


def test_consume_and_ack_after_sink_accepts():
    broker = MiniAmqpBroker()
    got = []
    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1")
    rx.sink = got.append
    rx.start()
    try:
        assert wait_until(lambda: broker.sessions == 1)
        broker.push(b'{"deviceToken":"d1"}')
        broker.push(b'{"deviceToken":"d2"}')
        assert wait_until(lambda: len(got) == 2)
        assert got == [b'{"deviceToken":"d1"}', b'{"deviceToken":"d2"}']
        assert wait_until(lambda: broker.acks == [1, 2])
        assert broker.declares == ["q1"]
        assert broker.auth[0] == "PLAIN"
        assert broker.auth[1] == b"\x00guest\x00guest"
    finally:
        rx.stop()
        broker.close()


def test_multi_frame_body_reassembled():
    broker = MiniAmqpBroker(body_frame_size=7)
    got = []
    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1")
    rx.sink = got.append
    rx.start()
    try:
        payload = b"x" * 100 + b"tail"
        assert wait_until(lambda: broker.sessions == 1)
        broker.push(payload)
        assert wait_until(lambda: got == [payload])
        assert wait_until(lambda: broker.acks == [1])
    finally:
        rx.stop()
        broker.close()


def test_rejected_payload_nacked_with_requeue():
    """A sink failure nacks the delivery with requeue — leaving it
    unacked would strand it until connection close and eventually stall
    the consumer once ``prefetch`` failures accumulate.  At-least-once,
    never silent loss: no ack is ever sent for a failed payload."""
    broker = MiniAmqpBroker()

    def bad_sink(payload):
        raise RuntimeError("journal down")

    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1")
    rx.sink = bad_sink
    rx.start()
    try:
        assert wait_until(lambda: broker.sessions == 1)
        broker.push(b"poison")
        assert wait_until(lambda: rx.emit_errors >= 1)
        assert wait_until(lambda: len(broker.nacks) >= 1)
        assert broker.nacks[0] == (1, 0x02)  # requeue bit set
        time.sleep(0.1)
        assert broker.acks == []  # never acked a failed payload
        assert rx.nacked >= 1
    finally:
        rx.stop()
        broker.close()


def test_prefetch_window_survives_sink_failures():
    """Regression for the stall ADVICE flagged: with prefetch=2, more
    than two consecutive sink failures would freeze a consumer that
    never nacks.  With nack+requeue every delivery is eventually
    redelivered and lands once the sink recovers — nothing stalls,
    nothing is lost."""
    broker = MiniAmqpBroker()
    got = []
    fail = [True]

    def flaky_sink(payload):
        if fail[0]:
            raise RuntimeError("transient")
        got.append(payload)

    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1", prefetch=2)
    rx.sink = flaky_sink
    rx.start()
    try:
        assert wait_until(lambda: broker.sessions == 1)
        for i in range(4):  # > prefetch consecutive failures
            broker.push(b"fail-%d" % i)
        assert wait_until(lambda: rx.emit_errors >= 4)
        assert wait_until(lambda: len(broker.nacks) >= 4)
        fail[0] = False
        broker.push(b"good")
        # the sink recovered: the requeued deliveries AND the new one all
        # land (at-least-once), and everything delivered gets acked
        assert wait_until(lambda: sorted(got) == sorted(
            [b"fail-0", b"fail-1", b"fail-2", b"fail-3", b"good"]))
        assert wait_until(lambda: len(broker.acks) == 5)
        assert rx._nack_streak == 0  # streak resets on success
    finally:
        rx.stop()
        broker.close()


def test_persistent_sink_failure_backs_off_not_spins():
    """A sink that keeps failing must not turn nack+redeliver into a
    tight spin: the escalating pre-nack delay (50 ms doubling to 1 s)
    bounds the retry rate to a handful per second."""
    broker = MiniAmqpBroker()

    def dead_sink(payload):
        raise RuntimeError("persistently down")

    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1")
    rx.sink = dead_sink
    rx.start()
    try:
        assert wait_until(lambda: broker.sessions == 1)
        broker.push(b"poison")
        assert wait_until(lambda: rx.emit_errors >= 1)
        time.sleep(1.0)
        # with backoff 50+100+200+400+800ms ≈ 5 attempts fit in ~1.5s;
        # without it the redeliver loop would spin hundreds of times
        assert rx.emit_errors <= 8
        assert rx._nack_streak >= 2  # it IS being redelivered + retried
    finally:
        rx.stop()
        broker.close()


def test_delivery_coalesced_with_consume_ok_not_dropped():
    """Regression for the frame-drop ADVICE flagged: a delivery the
    broker coalesces into the same TCP segment as basic.consume-ok must
    reach the sink and be acked, not die inside the handshake parser."""
    broker = MiniAmqpBroker(coalesce_first_delivery=True)
    broker.push(b"early-bird")  # queued BEFORE the receiver connects
    broker.push(b"second")
    got = []
    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1")
    rx.sink = got.append
    rx.start()
    try:
        assert wait_until(lambda: got == [b"early-bird", b"second"])
        assert wait_until(lambda: broker.acks == [1, 2])
        # and the session keeps working for normal deliveries after
        broker.push(b"third")
        assert wait_until(lambda: b"third" in got)
    finally:
        rx.stop()
        broker.close()


def test_reconnects_after_dropped_session():
    broker = MiniAmqpBroker(drop_first_session=True)
    got = []
    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1",
                      reconnect_delay_s=0.05)
    rx.sink = got.append
    rx.start()
    try:
        assert wait_until(lambda: broker.sessions >= 2)
        broker.push(b"after-reconnect")
        assert wait_until(lambda: got == [b"after-reconnect"])
    finally:
        rx.stop()
        broker.close()


def test_receiver_feeds_instance_pipeline(tmp_path):
    """End-to-end: AMQP delivery → source decode → dispatcher → store."""
    from sitewhere_tpu.ingest.sources import InboundEventSource
    from sitewhere_tpu.ingest.decoders import JsonDecoder
    from tests.test_instance import make_config, seed_device
    from sitewhere_tpu.instance import Instance

    inst = Instance(make_config(tmp_path))
    inst.start()
    broker = MiniAmqpBroker()
    rx = AmqpReceiver("127.0.0.1", broker.port, queue="events")
    source = InboundEventSource(
        source_id="amqp", receivers=[rx], decoder=JsonDecoder(),
        on_event=inst.dispatcher.ingest,
        on_registration=inst.dispatcher.ingest_registration,
        on_failed_decode=inst.dispatcher.ingest_failed_decode,
    )
    try:
        seed_device(inst)
        source.start()
        assert wait_until(lambda: broker.sessions == 1)
        broker.push(
            b'{"deviceToken":"dev-1","type":"Measurement",'
            b'"request":{"name":"temp","value":21.5,"eventDate":1000}}')
        assert wait_until(lambda: broker.acks == [1])
        inst.dispatcher.flush()
        inst.event_store.flush()
        assert inst.event_store.total_events == 1
    finally:
        source.stop()
        broker.close()
        inst.stop()
        inst.terminate()


def test_heartbeat_negotiated_and_dead_connection_detected():
    """With a negotiated heartbeat, a broker that goes silent after the
    handshake is declared dead within ~2 intervals and the receiver
    reconnects instead of hanging forever."""
    broker = MiniAmqpBroker(heartbeat=1)
    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1",
                      heartbeat_s=1, reconnect_delay_s=0.05)
    rx.sink = lambda p: None
    rx.start()
    try:
        assert wait_until(lambda: broker.sessions >= 1)
        # the mini-broker never sends heartbeats, so the receiver's
        # 2x-interval cutoff fires and it reconnects — session count
        # keeps climbing without any traffic
        assert wait_until(lambda: broker.sessions >= 2)
    finally:
        rx.stop()
        broker.close()


def test_socket_drop_mid_stream_reconnects_and_resumes():
    """A session that dies after one delivery (socket closed mid-stream)
    triggers reconnect; consumption resumes on the fresh session."""

    broker = MiniAmqpBroker()
    got = []
    rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1",
                      reconnect_delay_s=0.05)
    rx.sink = got.append
    rx.start()
    try:
        assert wait_until(lambda: broker.sessions == 1)
        broker.push(b"one")
        assert wait_until(lambda: got == [b"one"])
        # kill the live session socket only (the accept loop stays up):
        sock = rx._sock
        assert sock is not None
        sock.close()
        assert wait_until(lambda: broker.sessions >= 2)
        broker.push(b"two")
        assert wait_until(lambda: b"two" in got)
    finally:
        rx.stop()
        broker.close()

"""AMQP 1.0 / Event Hub receiver against a scripted mini-broker.

Mirrors the 0-9-1 strategy (test_amqp.py): a real-socket server speaks
the server side of the subset — SASL, open/begin/attach, flow credit,
Event-Hub-shaped transfers (x-opt-offset annotations + data sections),
dispositions — so the client's wire behavior is pinned end-to-end
without an Azure dependency.
"""

import json
import socket
import struct
import threading

import pytest

from sitewhere_tpu.ingest.amqp10 import (
    ACCEPTED,
    AMQP_HEADER,
    ATTACH,
    BEGIN,
    DISPOSITION,
    EventHubReceiver,
    FLOW,
    FRAME_SASL,
    FrameReader,
    OFFSET_ANNOTATION,
    OPEN,
    SASL_HEADER,
    SASL_INIT,
    SASL_MECHANISMS,
    SASL_OUTCOME,
    SEC_DATA,
    SEC_MESSAGE_ANN,
    SELECTOR_FILTER,
    Described,
    Symbol,
    TRANSFER,
    _Uint,
    _Ulong,
    amqp_frame,
    decode_value,
    encode_value,
    parse_frame_body,
    parse_message,
    performative,
)
from sitewhere_tpu.netio import close_socket

from time_limit import wait_until


def test_codec_round_trips():
    values = [
        None, True, False, 0, 1, -1, 127, -128, 1 << 40, -(1 << 40),
        3.5, "hello", "x" * 300, b"bytes", b"y" * 300,
        Symbol("sym"), [], [1, "two", None], {"k": "v", Symbol("s"): 7},
        Described(_Ulong(0x75), b"payload"),
        [Described(_Ulong(0x28), ["addr", None, None])],
    ]
    for v in values:
        buf = encode_value(v)
        out, off = decode_value(buf, 0)
        assert off == len(buf), v
        if isinstance(v, _Ulong):
            v = int(v)
        assert out == v, (v, out)


def encode_event_hub_message(payload: bytes, offset: str) -> bytes:
    """Annotations section (x-opt-offset) + one data section."""
    return (
        b"\x00" + encode_value(_Ulong(SEC_MESSAGE_ANN))
        + encode_value({Symbol(OFFSET_ANNOTATION): offset})
        + b"\x00" + encode_value(_Ulong(SEC_DATA)) + encode_value(payload)
    )


class MiniEventHub:
    """Server side of the AMQP 1.0 subset, one partition link."""

    def __init__(self, messages=None, expect_plain=None, drop_after=None,
                 split_transfer=False, pipeline_after_sasl=False):
        self.messages = list(messages or [])
        self.expect_plain = expect_plain  # (user, password) or None
        self.drop_after = drop_after      # close socket after N transfers
        self.split_transfer = split_transfer
        # coalesce sasl-outcome + the AMQP protocol header into ONE send
        # (AMQP 1.0 permits the server to pipeline the next layer)
        self.pipeline_after_sasl = pipeline_after_sasl
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(4)
        self.port = self.sock.getsockname()[1]
        self.sessions = 0
        self.dispositions = []
        self.attach_sources = []
        self.flow_credits = []
        # delivered-but-unsettled (payload, offset): requeued at the next
        # session start, the broker-side at-least-once half of the contract
        self._unsettled = {}
        self._next_offset = 0
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def push(self, payload: bytes):
        self.messages.append(payload)

    def close(self):
        self._stop = True
        close_socket(self.sock)   # wakes the blocked accept

    # -- protocol ------------------------------------------------------------

    def _recv_perf(self, conn, reader, pending, want):
        while True:
            while pending:
                ftype, channel, body = pending.pop(0)
                perf, payload = parse_frame_body(body)
                if perf is None:
                    continue
                assert perf.descriptor == want, (
                    f"want 0x{want:02x} got 0x{perf.descriptor:02x}")
                return perf
            data = conn.recv(65536)
            if not data:
                raise ConnectionError("client gone")
            pending.extend(reader.feed(data))

    def _loop(self):
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            try:
                self._session(conn)
            except (ConnectionError, OSError, AssertionError):
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def _session(self, conn):
        conn.settimeout(10)
        self.sessions += 1
        reader = FrameReader()
        pending = []
        header = conn.recv(8)
        if header == SASL_HEADER:
            conn.sendall(SASL_HEADER)
            conn.sendall(amqp_frame(0, performative(
                SASL_MECHANISMS,
                [[Symbol("PLAIN"), Symbol("ANONYMOUS")]]), FRAME_SASL))
            init = self._recv_perf(conn, reader, pending, SASL_INIT)
            if self.expect_plain is not None:
                mech, resp = init.value[0], init.value[1]
                assert str(mech) == "PLAIN"
                user, pw = self.expect_plain
                assert resp == b"\x00" + user.encode() + b"\x00" + pw.encode()
            outcome = amqp_frame(0, performative(
                SASL_OUTCOME, [0, None]), FRAME_SASL)
            if self.pipeline_after_sasl:
                # one segment: outcome + our AMQP header, pipelined
                conn.sendall(outcome + AMQP_HEADER)
            else:
                conn.sendall(outcome)
            reader = FrameReader()
            pending = []
            header = conn.recv(8)
        assert header == AMQP_HEADER, header
        if not self.pipeline_after_sasl:
            conn.sendall(AMQP_HEADER)
        self._recv_perf(conn, reader, pending, OPEN)
        conn.sendall(amqp_frame(0, performative(OPEN, [
            "mini-eventhub", None, _Uint(1 << 20), _Uint(0), _Uint(30000)])))
        self._recv_perf(conn, reader, pending, BEGIN)
        conn.sendall(amqp_frame(0, performative(BEGIN, [
            _Uint(0), _Uint(0), _Uint(2048), _Uint(2048)])))
        attach = self._recv_perf(conn, reader, pending, ATTACH)
        self.attach_sources.append(attach.value[5])
        conn.sendall(amqp_frame(0, performative(ATTACH, [
            attach.value[0], _Uint(0), False, None, None,
            attach.value[5], None, None, None, _Uint(0)])))

        credit = 0
        delivery_id = 0
        sent = 0
        # redeliver what the previous session left unsettled, in order
        redelivery = sorted(self._unsettled.values(), key=lambda po: po[1])
        self._unsettled = {}
        conn.settimeout(0.05)
        while not self._stop:
            # drain client frames (flow / disposition)
            try:
                data = conn.recv(65536)
                if not data:
                    return
                pending.extend(reader.feed(data))
            except socket.timeout:
                pass
            while pending:
                ftype, channel, body = pending.pop(0)
                perf, _ = parse_frame_body(body)
                if perf is None:
                    continue
                if perf.descriptor == FLOW:
                    credit = int(perf.value[6])
                    self.flow_credits.append(credit)
                elif perf.descriptor == DISPOSITION:
                    state = perf.value[4]
                    assert isinstance(state, Described)
                    assert state.descriptor == ACCEPTED
                    did = int(perf.value[1])
                    self.dispositions.append(did)
                    self._unsettled.pop(did, None)
            while (redelivery or self.messages) and credit > 0:
                if redelivery:
                    payload, off = redelivery.pop(0)
                else:
                    payload = self.messages.pop(0)
                    off = str(1000 + self._next_offset)
                    self._next_offset += 1
                self._unsettled[delivery_id] = (payload, off)
                msg = encode_event_hub_message(payload, off)
                # transfer: handle, delivery-id, delivery-tag,
                # message-format, settled, more
                if self.split_transfer and len(msg) > 8:
                    head = performative(TRANSFER, [
                        _Uint(0), _Uint(delivery_id),
                        struct.pack(">I", delivery_id), _Uint(0), False,
                        True])
                    conn.sendall(amqp_frame(0, head + msg[:8]))
                    tail = performative(TRANSFER, [
                        _Uint(0), _Uint(delivery_id),
                        struct.pack(">I", delivery_id), _Uint(0), False,
                        False])
                    conn.sendall(amqp_frame(0, tail + msg[8:]))
                else:
                    head = performative(TRANSFER, [
                        _Uint(0), _Uint(delivery_id),
                        struct.pack(">I", delivery_id), _Uint(0), False,
                        False])
                    conn.sendall(amqp_frame(0, head + msg))
                delivery_id += 1
                credit -= 1
                sent += 1
                if self.drop_after is not None and sent >= self.drop_after:
                    return  # simulate a dropped session


def make_receiver(broker, tmp_path=None, **kw):
    kw.setdefault("sasl", "anonymous")
    kw.setdefault("credit", 8)
    kw.setdefault("reconnect_delay_s", 0.05)
    r = EventHubReceiver("127.0.0.1", broker.port, event_hub="hub",
                         checkpoint_dir=(str(tmp_path) if tmp_path else None),
                         **kw)
    return r


def test_consume_settle_and_checkpoint(tmp_path):
    broker = MiniEventHub(messages=[b"one", b"two", b"three"])
    seen = []
    r = make_receiver(broker, tmp_path)
    r.sink = seen.append
    r.start()
    try:
        assert wait_until(lambda: seen == [b"one", b"two", b"three"])
        assert wait_until(lambda: broker.dispositions == [0, 1, 2])
        # offsets checkpointed per partition
        ckpt = json.load(open(r._ckpt_path()))
        assert ckpt == {"0": "1002"}
    finally:
        r.stop()
        broker.close()


def test_sasl_plain_credentials_verified(tmp_path):
    broker = MiniEventHub(messages=[b"hi"],
                          expect_plain=("user", "secret"))
    seen = []
    r = make_receiver(broker, tmp_path, sasl="plain",
                      username="user", password="secret")
    r.sink = seen.append
    r.start()
    try:
        assert wait_until(lambda: seen == [b"hi"])
    finally:
        r.stop()
        broker.close()


def test_multi_frame_transfer_reassembled(tmp_path):
    broker = MiniEventHub(messages=[b"a-long-payload-split-across-frames"],
                          split_transfer=True)
    seen = []
    r = make_receiver(broker, tmp_path)
    r.sink = seen.append
    r.start()
    try:
        assert wait_until(lambda: seen == [b"a-long-payload-split-across-frames"])
    finally:
        r.stop()
        broker.close()


def test_credit_topped_up_past_initial_window(tmp_path):
    n = 40  # >> credit window of 8
    broker = MiniEventHub(messages=[b"m%d" % i for i in range(n)])
    seen = []
    r = make_receiver(broker, tmp_path)
    r.sink = seen.append
    r.start()
    try:
        assert wait_until(lambda: len(seen) == n)
        assert seen == [b"m%d" % i for i in range(n)]
        assert len(broker.flow_credits) > 1  # replenished at half-window
    finally:
        r.stop()
        broker.close()


def test_reconnect_resumes_from_checkpoint(tmp_path):
    broker = MiniEventHub(messages=[b"m0", b"m1", b"m2", b"m3"],
                          drop_after=2)
    seen = []
    r = make_receiver(broker, tmp_path)
    r.sink = seen.append
    r.start()
    try:
        assert wait_until(lambda: broker.sessions >= 2 and len(seen) >= 4)
        # second attach carried the Event-Hub selector filter past m1
        assert len(broker.attach_sources) >= 2
        filt = broker.attach_sources[1].value[7]
        sel = filt[Symbol(SELECTOR_FILTER)]
        assert isinstance(sel, Described)
        assert sel.value == (
            f"amqp.annotation.{OFFSET_ANNOTATION} > '1001'")
    finally:
        r.stop()
        broker.close()


def test_receiver_feeds_instance_pipeline(tmp_path):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    lines = [json.dumps({
        "deviceToken": "eh-1", "type": "Measurement",
        "request": {"name": "temp", "value": 20.0 + i,
                    "eventDate": 1_753_000_000 + i},
    }).encode() for i in range(3)]
    # published once the device exists: a line that beats create_device
    # to the pipeline is an unregistered device's, and never accepted
    broker = MiniEventHub()
    cfg = Config({
        "instance": {"id": "eh-test", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 256,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "sources": [{"id": "eh", "receivers": [{
            "type": "eventhub", "host": "127.0.0.1", "port": broker.port,
            "event_hub": "hub", "sasl": "anonymous", "credit": 8,
            "checkpoint_dir": str(tmp_path / "ckpt"),
        }]}],
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    try:
        inst.device_management.create_device_type(token="sensor",
                                                  name="Sensor")
        inst.device_management.create_device(token="eh-1",
                                             device_type="sensor")
        inst.device_management.create_device_assignment(device="eh-1")
        for line in lines:
            broker.push(line)
        assert wait_until(
            lambda: inst.dispatcher.metrics_snapshot()["accepted"] == 3)
        inst.dispatcher.flush()
        inst.event_store.flush()
        assert inst.event_store.total_events == 3
    finally:
        inst.stop()
        inst.terminate()
        broker.close()


def test_sink_failure_leaves_unsettled_and_recycles(tmp_path):
    broker = MiniEventHub(messages=[b"bad", b"good"])
    seen = []
    fails = {"n": 0}

    def flaky(payload):
        if payload == b"bad" and fails["n"] < 1:
            fails["n"] += 1
            raise RuntimeError("journal down")
        seen.append(payload)

    r = make_receiver(broker, tmp_path)
    r.sink = flaky
    r.start()
    try:
        # the failed delivery is NOT settled, so the recycled session
        # redelivers it (at-least-once) and it succeeds the second time
        assert wait_until(lambda: seen == [b"bad", b"good"])
        assert r.emit_errors == 1
        assert broker.sessions >= 2
    finally:
        r.stop()
        broker.close()


def test_server_pipelining_amqp_header_after_sasl(tmp_path):
    """AMQP 1.0 permits the server to pipeline its protocol header (and
    beyond) behind sasl-outcome in one TCP segment; the SASL phase must
    not consume or misparse bytes past the outcome frame boundary."""
    broker = MiniEventHub(messages=[b"pipelined"], pipeline_after_sasl=True)
    seen = []
    r = make_receiver(broker, tmp_path)
    r.sink = seen.append
    r.start()
    try:
        assert wait_until(lambda: seen == [b"pipelined"])
        assert broker.sessions == 1  # no failed connect/reconnect spin
    finally:
        r.stop()
        broker.close()

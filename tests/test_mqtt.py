"""MQTT client conformance against an in-test mini broker (stdlib only).

The fake broker implements just enough of MQTT 3.1.1 server behavior to
validate our client's wire format: CONNECT/CONNACK, SUBSCRIBE/SUBACK,
PUBLISH fan-out (QoS 0/1), PUBACK, DISCONNECT.
"""

import socket
import socketserver
import struct
import threading

from sitewhere_tpu.ingest.mqtt import (
    CONNACK,
    CONNECT,
    DISCONNECT,
    PUBACK,
    PUBLISH,
    SUBACK,
    SUBSCRIBE,
    MqttClient,
    parse_publish,
    read_packet,
    write_publish,
)

from time_limit import wait_until


class MiniBroker:
    def __init__(self):
        self.subscribers = []  # (sock, topic_filter)
        self.published = []    # (topic, payload, qos)
        self.pubacks = []      # packet ids acked by clients
        self.conns = []        # every client connection, for close()
        self.lock = threading.Lock()
        broker = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                sock = self.request
                with broker.lock:
                    broker.conns.append(sock)
                try:
                    while True:
                        ptype, flags, body = read_packet(sock)
                        if ptype == CONNECT:
                            sock.sendall(bytes([CONNACK << 4, 2, 0, 0]))
                        elif ptype == SUBSCRIBE:
                            (pid,) = struct.unpack_from(">H", body, 0)
                            (tlen,) = struct.unpack_from(">H", body, 2)
                            topic = body[4:4 + tlen].decode()
                            with broker.lock:
                                broker.subscribers.append((sock, topic))
                            sock.sendall(bytes([SUBACK << 4, 3]) +
                                         struct.pack(">H", pid) + b"\x00")
                        elif ptype == PUBLISH:
                            topic, payload, qos, pid = parse_publish(flags, body)
                            with broker.lock:
                                broker.published.append((topic, payload, qos))
                                subs = list(broker.subscribers)
                            if qos == 1:
                                sock.sendall(bytes([PUBACK << 4, 2]) +
                                             struct.pack(">H", pid))
                            for ssock, tfilter in subs:
                                if tfilter == topic or tfilter == "#":
                                    write_publish(ssock, topic, payload, 0)
                        elif ptype == PUBACK:
                            with broker.lock:
                                broker.pubacks.append(
                                    struct.unpack(">H", body)[0])
                        elif ptype == DISCONNECT:
                            return
                except Exception:
                    return

        socketserver.ThreadingTCPServer.allow_reuse_address = True
        self.server = socketserver.ThreadingTCPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        self.thread = threading.Thread(target=self.server.serve_forever, daemon=True)
        self.thread.start()

    def close(self):
        self.server.shutdown()
        # server_close() joins the handlers: wake any still reading a
        # client that never disconnected
        with self.lock:
            conns = list(self.conns)
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        self.server.server_close()


def test_connect_subscribe_publish_roundtrip():
    broker = MiniBroker()
    try:
        got = []
        sub = MqttClient("127.0.0.1", broker.port, client_id="sub")
        sub.on_message = lambda t, p: got.append((t, p))
        sub.connect()
        sub.subscribe("sitewhere/input")

        pub = MqttClient("127.0.0.1", broker.port, client_id="pub")
        pub.connect()
        pub.publish("sitewhere/input", b"hello-0", qos=0)
        pub.publish("sitewhere/input", b"hello-1", qos=1)
        pub.publish("other/topic", b"not-for-us", qos=0)

        assert wait_until(lambda: len(got) == 2)
        assert got == [("sitewhere/input", b"hello-0"),
                       ("sitewhere/input", b"hello-1")]
        # the broker may take the third publish after the second is out
        assert wait_until(lambda: len(broker.published) == 3)
        assert broker.published[-1][0] == "other/topic"
        pub.disconnect()
        sub.disconnect()
    finally:
        broker.close()


def test_mqtt_receiver_through_source():
    import json
    from sitewhere_tpu.ingest.decoders import JsonDecoder
    from sitewhere_tpu.ingest.sources import InboundEventSource, MqttReceiver

    broker = MiniBroker()
    try:
        events = []
        src = InboundEventSource(
            "mqtt-src",
            [MqttReceiver("127.0.0.1", broker.port, topic="sw/in")],
            JsonDecoder(),
            on_event=lambda req, raw: events.append(req),
        )
        src.start()
        pub = MqttClient("127.0.0.1", broker.port, client_id="dev")
        pub.connect()
        pub.publish("sw/in", json.dumps({
            "deviceToken": "mq-dev", "type": "Measurement",
            "request": {"name": "rpm", "value": 1200.0},
        }).encode())
        assert wait_until(lambda: len(events) == 1)
        assert events[0].device_token == "mq-dev"
        assert events[0].value == 1200.0
        pub.disconnect()
        src.stop()
    finally:
        broker.close()


def test_qos1_puback_sent_by_client():
    """Broker-side QoS1 delivery: client must PUBACK."""
    broker = MiniBroker()
    try:
        got = []
        sub = MqttClient("127.0.0.1", broker.port, client_id="q1")
        sub.on_message = lambda t, p: got.append(p)
        sub.connect()
        sub.subscribe("t", qos=1)
        # Deliver a QoS1 publish directly to the subscriber socket; the
        # broker's handler thread records the client's PUBACK.
        with broker.lock:
            ssock = broker.subscribers[0][0]
        write_publish(ssock, "t", b"payload", qos=1, packet_id=77)
        assert wait_until(lambda: got == [b"payload"])
        assert wait_until(lambda: 77 in broker.pubacks)
        sub.disconnect()
    finally:
        broker.close()

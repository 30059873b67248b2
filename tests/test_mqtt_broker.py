"""Hosted in-process MQTT broker: devices connect with no middleware.

Reference behavior covered: ``ActiveMQBrokerEventReceiver.java`` — the
platform embeds the broker, devices connect directly, inbound messages
feed the event source.  The device side here is the repo's own
``MqttClient``, so both halves of the 3.1.1 subset exercise each other
over a real socket.
"""

import socket
import struct
import threading
import time

import pytest

from sitewhere_tpu.ingest.mqtt import MqttClient, MqttError
from sitewhere_tpu.ingest.mqtt_broker import (
    MqttBroker,
    MqttBrokerReceiver,
    topic_matches,
)

from time_limit import wait_until


class TestTopicMatching:
    @pytest.mark.parametrize("filt,topic,want", [
        ("a/b/c", "a/b/c", True),
        ("a/b/c", "a/b/d", False),
        ("a/+/c", "a/b/c", True),
        ("a/+/c", "a/b/d/c", False),
        ("a/#", "a/b/c/d", True),
        ("a/#", "a", True),  # '#' includes the parent level (4.7.1-2)
        ("b/#", "a", False),
        ("#", "anything/at/all", True),
        ("+", "one", True),
        ("+", "one/two", False),
        ("sitewhere/input/#", "sitewhere/input/dev-1", True),
        ("sitewhere/input/#", "sitewhere/output/dev-1", False),
        ("#", "$SYS/broker", False),   # MQTT-4.7.2-1
        ("+/monitor", "$SYS/monitor", False),
        ("$SYS/#", "$SYS/broker", True),
    ])
    def test_wildcards(self, filt, topic, want):
        assert topic_matches(filt, topic) is want


def test_device_publishes_into_hosted_broker():
    rx = MqttBrokerReceiver(topic_filter="sitewhere/input/#")
    got = []
    rx.sink = got.append
    rx.start()
    try:
        dev = MqttClient("127.0.0.1", rx.port, client_id="dev-1")
        dev.connect()
        dev.publish("sitewhere/input/dev-1", b'{"deviceToken":"dev-1"}')
        dev.publish("sitewhere/other/dev-1", b"ignored")  # filter miss
        assert wait_until(lambda: rx.broker.published == 2)
        assert got == [b'{"deviceToken":"dev-1"}']
        dev.disconnect()
        assert wait_until(lambda: rx.broker.session_count == 0)
    finally:
        rx.stop()


def test_qos1_publish_gets_puback():
    rx = MqttBrokerReceiver()
    got = []
    rx.sink = got.append
    rx.start()
    try:
        dev = MqttClient("127.0.0.1", rx.port, client_id="dev-q1")
        dev.connect()
        # raw check: QoS1 publish must be PUBACKed with the same pid
        from sitewhere_tpu.ingest import mqtt as m
        sock = dev._sock
        m.write_publish(sock, "sitewhere/input/x", b"p1", qos=1,
                        packet_id=77)
        # the client pump consumes the PUBACK; assert delivery instead
        assert wait_until(lambda: got == [b"p1"])
        dev.disconnect()
    finally:
        rx.stop()


def test_fanout_between_subscribed_clients():
    """The hosted broker is a real (subset) broker: a second client
    subscribing sees what devices publish, at min(pub, sub) qos."""
    broker = MqttBroker()
    broker.start()
    try:
        sub = MqttClient("127.0.0.1", broker.port, client_id="observer")
        seen = []
        sub.on_message = lambda t, p: seen.append((t, p))
        sub.connect()
        sub.subscribe("fleet/+/telemetry", qos=1)

        dev = MqttClient("127.0.0.1", broker.port, client_id="dev-2")
        dev.connect()
        dev.publish("fleet/dev-2/telemetry", b"t0", qos=0)
        dev.publish("fleet/dev-2/telemetry", b"t1", qos=1)
        dev.publish("fleet/dev-2/status", b"nope", qos=0)
        assert wait_until(lambda: len(seen) == 2)
        assert seen == [("fleet/dev-2/telemetry", b"t0"),
                        ("fleet/dev-2/telemetry", b"t1")]
        assert broker.delivered == 2
        dev.disconnect()
        sub.disconnect()
    finally:
        broker.stop()


def test_client_id_takeover_replaces_old_session():
    broker = MqttBroker()
    broker.start()
    try:
        first = MqttClient("127.0.0.1", broker.port, client_id="same-id")
        first.connect()
        assert wait_until(lambda: broker.session_count == 1)
        second = MqttClient("127.0.0.1", broker.port, client_id="same-id")
        second.connect()
        # wait for the second CONNECT to be processed FIRST — session_count
        # is 1 both before and after the takeover, so waiting on it alone
        # races the broker's accept loop
        assert wait_until(lambda: broker.connects == 2)
        # old socket is closed by the broker (MQTT-3.1.4-2)
        assert wait_until(lambda: broker.session_count == 1)
        second.publish("t", b"alive")
        second.disconnect()
        first.disconnect()
    finally:
        broker.stop()


def test_unsubscribe_stops_delivery():
    broker = MqttBroker()
    broker.start()
    try:
        sub = MqttClient("127.0.0.1", broker.port, client_id="s")
        seen = []
        sub.on_message = lambda t, p: seen.append(p)
        sub.connect()
        sub.subscribe("a/b")
        pub = MqttClient("127.0.0.1", broker.port, client_id="p")
        pub.connect()
        pub.publish("a/b", b"one")
        assert wait_until(lambda: seen == [b"one"])
        # UNSUBSCRIBE over the raw socket (the client has no helper)
        from sitewhere_tpu.ingest import mqtt as m
        body = struct.pack(">H", 9) + m._utf8("a/b")
        with sub._lock:
            sub._sock.sendall(bytes([m.UNSUBSCRIBE << 4 | 0x02])
                              + m._encode_remaining(len(body)) + body)
        time.sleep(0.2)
        pub.publish("a/b", b"two")
        time.sleep(0.3)
        assert seen == [b"one"]
        pub.disconnect()
        sub.disconnect()
    finally:
        broker.stop()


def test_bad_protocol_level_refused():
    broker = MqttBroker()
    broker.start()
    try:
        sock = socket.create_connection(("127.0.0.1", broker.port))
        from sitewhere_tpu.ingest import mqtt as m
        body = m._utf8("MQTT") + bytes([3, 0x02]) + struct.pack(">H", 0)
        body += m._utf8("old-client")
        sock.sendall(bytes([m.CONNECT << 4])
                     + m._encode_remaining(len(body)) + body)
        ptype, _, ack = m.read_packet(sock)
        assert ptype == m.CONNACK
        assert ack[1] == 0x01  # unacceptable protocol level
        sock.close()
        assert broker.session_count == 0
    finally:
        broker.stop()


def test_keepalive_timeout_reaps_dead_session():
    broker = MqttBroker()
    broker.start()
    try:
        # hand-rolled CONNECT with a 1s keepalive, then silence
        sock = socket.create_connection(("127.0.0.1", broker.port))
        from sitewhere_tpu.ingest import mqtt as m
        body = m._utf8("MQTT") + bytes([4, 0x02]) + struct.pack(">H", 1)
        body += m._utf8("silent")
        sock.sendall(bytes([m.CONNECT << 4])
                     + m._encode_remaining(len(body)) + body)
        ptype, _, ack = m.read_packet(sock)
        assert (ptype, ack[1]) == (m.CONNACK, 0)
        assert broker.session_count == 1
        # no pings: the broker must reap within ~1.5x keepalive
        assert wait_until(lambda: broker.session_count == 0)
        sock.close()
    finally:
        broker.stop()


def test_broker_receiver_feeds_instance_pipeline(tmp_path):
    """End-to-end, middleware-free: device MQTT publish → hosted broker
    → source decode → dispatcher → event store."""
    from sitewhere_tpu.ingest.sources import InboundEventSource
    from sitewhere_tpu.ingest.decoders import JsonDecoder
    from tests.test_instance import make_config, seed_device
    from sitewhere_tpu.instance import Instance

    inst = Instance(make_config(tmp_path))
    inst.start()
    rx = MqttBrokerReceiver(topic_filter="sitewhere/input/#")
    source = InboundEventSource(
        source_id="hosted-mqtt", receivers=[rx], decoder=JsonDecoder(),
        on_event=inst.dispatcher.ingest,
        on_registration=inst.dispatcher.ingest_registration,
        on_failed_decode=inst.dispatcher.ingest_failed_decode,
    )
    try:
        seed_device(inst)
        source.start()
        dev = MqttClient("127.0.0.1", rx.port, client_id="dev-1")
        dev.connect()
        dev.publish(
            "sitewhere/input/dev-1",
            b'{"deviceToken":"dev-1","type":"Measurement",'
            b'"request":{"name":"temp","value":21.5,"eventDate":1000}}',
            qos=1)
        assert wait_until(lambda: rx.received_count == 1)
        # received_count ticks BEFORE the sink runs (Receiver._emit);
        # wait for admission too, or the flush below can race the
        # broker-session thread's ingest and observe an empty store
        assert wait_until(
            lambda: inst.dispatcher.metrics_snapshot()["accepted"] >= 1)
        inst.dispatcher.flush()
        inst.event_store.flush()
        assert inst.event_store.total_events == 1
        dev.disconnect()
    finally:
        source.stop()
        inst.stop()
        inst.terminate()


def test_factory_builds_hosted_broker_source():
    from sitewhere_tpu.ingest.factory import build_sources

    sources = build_sources([
        {"id": "fleet", "decoder": "json",
         "receivers": [{"type": "mqtt-broker",
                        "topic_filter": "fleet/#"}]},
    ])
    assert len(sources) == 1
    rx = sources[0].receivers[0]
    assert isinstance(rx, MqttBrokerReceiver)
    assert rx.topic_filter == "fleet/#"


def test_burst_publish_then_disconnect_loses_nothing():
    """A device that fires N QoS-1 publishes and immediately disconnects
    must lose none: the client drains outstanding PUBACKs before closing
    (publisher-side at-least-once), and the broker delivers to its taps
    BEFORE acking, so an EPIPE on the ack can never drop a message."""
    broker = MqttBroker()
    broker.start()
    seen = []
    broker.on_publish.append(lambda t, p: seen.append(p))
    try:
        for round_no in range(5):
            c = MqttClient("127.0.0.1", broker.port,
                           client_id=f"burst-{round_no}")
            c.connect()
            for i in range(20):
                c.publish("fleet/burst/events",
                          b"m%d-%d" % (round_no, i), qos=1)
            c.disconnect()  # immediately — no settling sleep
        assert wait_until(lambda: len(seen) == 100)
        assert seen == [b"m%d-%d" % (r, i)
                        for r in range(5) for i in range(20)]
    finally:
        broker.stop()


def test_command_delivery_through_hosted_broker(tmp_path):
    """The no-middleware fleet story is BIDIRECTIONAL: a device connected
    to the instance's HOSTED broker publishes telemetry in and receives
    command invocations back over the same broker socket, then
    acknowledges — closing the invocation↔response correlation loop with
    no external middleware anywhere."""
    import json as _json
    import queue

    from sitewhere_tpu.commands import (
        CommandDestination,
        JsonCommandEncoder,
        MqttDeliveryProvider,
        TopicParameterExtractor,
    )
    from sitewhere_tpu.ingest.decoders import JsonDecoder
    from sitewhere_tpu.ingest.sources import InboundEventSource
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.schema import EventType
    from tests.test_instance import make_config

    inst = Instance(make_config(tmp_path))
    inst.start()
    rx = MqttBrokerReceiver(topic_filter="sitewhere/input/#")
    source = InboundEventSource(
        source_id="hosted-mqtt", receivers=[rx], decoder=JsonDecoder(),
        on_event=inst.dispatcher.ingest,
        on_registration=inst.dispatcher.ingest_registration,
        on_failed_decode=inst.dispatcher.ingest_failed_decode,
    )
    dev = None
    try:
        dm = inst.device_management
        dm.create_device_type(token="s", name="S")
        dm.create_device_command("s", token="reboot", name="Reboot",
                                 namespace="sw")
        dm.create_device(token="dev-1", device_type="s")
        a = dm.create_device_assignment(device="dev-1")
        source.start()

        # command delivery LOOPS BACK through the hosted broker
        inst.commands.add_destination(CommandDestination(
            "hosted-mqtt", JsonCommandEncoder(), TopicParameterExtractor(),
            MqttDeliveryProvider("127.0.0.1", rx.port)))

        got: "queue.Queue" = queue.Queue()
        dev = MqttClient("127.0.0.1", rx.port, client_id="dev-1")
        dev.on_message = lambda topic, payload: got.put((topic, payload))
        dev.connect()
        dev.subscribe("sitewhere/command/dev-1", qos=0)

        out = inst.create_command_invocation(a.token, "reboot")
        inv_token = out["token"]
        topic, payload = got.get(timeout=10)
        assert topic == "sitewhere/command/dev-1"
        doc = _json.loads(payload)
        assert doc["command"] == "Reboot"
        assert doc["invocation"] == inv_token

        # the device acknowledges over the SAME broker
        dev.publish("sitewhere/input/dev-1", _json.dumps({
            "deviceToken": "dev-1", "type": "commandResponse",
            "request": {"originatingEventId": inv_token,
                        "response": "rebooted",
                        "eventDate": 1_753_800_300}}).encode(), qos=1)

        def correlated():
            inst.dispatcher.flush()
            handle = inst.identity.invocation.lookup(inv_token)
            if handle < 0:
                return False
            return inst.event_store.query(
                command_id=handle,
                event_type=int(EventType.COMMAND_RESPONSE)).total == 1

        assert wait_until(correlated)
        assert inst.commands.delivered == 1
    finally:
        if dev is not None:
            dev.disconnect()
        source.stop()
        inst.stop()
        inst.terminate()


def test_shed_pause_tied_to_negotiated_keepalive():
    """The per-session shed-pause deadline follows the keepalive: a
    session with keepalive K may pause up to the reaper's slack
    ((grace-1) * K); keepalive-0 sessions keep the broker-wide floor."""
    from sitewhere_tpu.ingest.mqtt_broker import MqttBroker, _Session

    broker = MqttBroker()
    chatty = _Session("chatty", socket.socket(), keepalive=60)
    quiet = _Session("quiet", socket.socket(), keepalive=0)
    short = _Session("short", socket.socket(), keepalive=1)
    try:
        # hint below every cap passes through unchanged
        assert broker.shed_pause_s(chatty, 0.1) == pytest.approx(0.1)
        # keepalive 60 @ grace 1.5 → 30s slack absorbs a long hint
        assert broker.shed_pause_s(chatty, 120.0) == pytest.approx(30.0)
        # no keepalive → conservative broker-wide floor
        assert broker.shed_pause_s(quiet, 120.0) == pytest.approx(
            broker.max_shed_pause_s)
        # short keepalives get their own (smaller) slack — still at
        # least the floor
        assert broker.shed_pause_s(short, 120.0) == pytest.approx(0.5)
        assert broker.shed_pause_s(short, 0.05) == pytest.approx(0.05)
    finally:
        for s in (chatty, quiet, short):
            s.close()


def test_shed_pause_applied_on_overload(monkeypatch):
    """An OverloadShed from the tap withholds the PUBACK and pauses for
    the keepalive-derived deadline, not the raw Retry-After hint."""
    import sitewhere_tpu.ingest.mqtt_broker as mb
    from sitewhere_tpu.runtime.overload import (
        OverloadShed,
        OverloadState,
        PriorityClass,
    )

    broker = MqttBroker()
    broker.start()
    try:
        def shed(topic, payload):
            raise OverloadShed(PriorityClass.TELEMETRY,
                               OverloadState.SHEDDING,
                               retry_after_s=120.0)

        broker.on_publish.append(shed)
        pauses = []
        real_sleep = time.sleep

        def fake_sleep(s):
            # record (and skip) the broker's long shed pause; small
            # sleeps — this test's own polling — run for real
            if s > 1.0:
                pauses.append(s)
                return
            real_sleep(s)

        monkeypatch.setattr(mb.time, "sleep", fake_sleep)
        client = MqttClient("127.0.0.1", broker.port,
                            client_id="dev-shed", keepalive=60)
        client.connect()
        client.publish("sitewhere/input/x", b"{}", qos=0)
        assert wait_until(lambda: pauses)
        assert pauses[0] == pytest.approx(30.0)
        assert broker.sheds == 1
        client.disconnect()
    finally:
        broker.stop()

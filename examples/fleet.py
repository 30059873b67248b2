"""Fleet without middleware: devices connect straight to the platform.

Run from the repo root on the backend JAX finds (``JAX_PLATFORMS=cpu``
for the CPU)::

    python examples/fleet.py

What it shows, end to end:

1. an :class:`~sitewhere_tpu.instance.Instance` HOSTING its own MQTT
   3.1.1 broker (config type ``mqtt-broker`` — the reference embeds
   ActiveMQ the same way): a simulated device fleet connects with the
   repo's own MQTT client and publishes JSON measurements, no external
   broker process anywhere;
2. the same instance consuming an Event-Hub-style AMQP 1.0 stream
   (config type ``eventhub``) — here served by the test suite's
   scripted mini-hub, standing in for an Azure Event Hubs partition —
   with per-partition offset checkpoints;
3. both streams land in the SAME pipeline: decode → journal → batcher
   → fused step → store/state, queried back at the end;
4. the loop runs BOTH ways with no middleware: a command invocation is
   delivered back to a connected device over the SAME hosted broker,
   the device acknowledges, and the ack correlates to the invocation.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import tempfile
import time

from sitewhere_tpu.ingest.mqtt import MqttClient
from sitewhere_tpu.instance import Instance
from sitewhere_tpu.runtime.config import Config

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))
from test_amqp10 import MiniEventHub  # noqa: E402  (scripted stand-in hub)


def main() -> None:
    hub_lines = [json.dumps({
        "deviceToken": f"cloud-{i}", "type": "Measurement",
        "request": {"name": "pressure", "value": 95.0 + i,
                    "eventDate": int(time.time())},
    }).encode() for i in range(4)]
    hub = MiniEventHub(messages=hub_lines)

    tmp = tempfile.mkdtemp(prefix="sw-fleet-")
    cfg = Config({
        "instance": {"id": "fleet-demo", "data_dir": os.path.join(tmp, "d")},
        "pipeline": {"width": 256, "registry_capacity": 1024,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "sources": [
            {"id": "edge", "receivers": [{
                "type": "mqtt-broker", "port": 0,
                "topic_filter": "fleet/+/events"}]},
            {"id": "cloud", "receivers": [{
                "type": "eventhub", "host": "127.0.0.1", "port": hub.port,
                "event_hub": "hub", "sasl": "anonymous",
                "checkpoint_dir": os.path.join(tmp, "ckpt")}]},
        ],
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    try:
        dm = inst.device_management
        dm.create_device_type(token="sensor", name="Sensor")
        dm.create_device_command("sensor", token="reboot", name="Reboot",
                                 namespace="fleet")
        assignments = {}
        for name in ([f"edge-{i}" for i in range(8)]
                     + [f"cloud-{i}" for i in range(4)]):
            dm.create_device(token=name, device_type="sensor")
            assignments[name] = dm.create_device_assignment(device=name)

        broker_port = inst.sources[0].receivers[0].broker.port
        print(f"hosted MQTT broker on :{broker_port}; "
              f"mini Event Hub on :{hub.port}")

        # the fleet: 8 devices connect DIRECTLY to the instance
        clients = []
        for i in range(8):
            c = MqttClient("127.0.0.1", broker_port, client_id=f"edge-{i}")
            c.connect()
            clients.append(c)
        for round_no in range(3):
            for i, c in enumerate(clients):
                c.publish(f"fleet/edge-{i}/events", json.dumps({
                    "deviceToken": f"edge-{i}", "type": "Measurement",
                    "request": {"name": "temp",
                                "value": 20.0 + round_no,
                                "eventDate": int(time.time())},
                }).encode(), qos=1)
        for c in clients:
            c.disconnect()

        deadline = time.monotonic() + 15
        want = 8 * 3 + len(hub_lines)
        while time.monotonic() < deadline:
            if inst.dispatcher.metrics_snapshot()["accepted"] >= want:
                break
            time.sleep(0.05)
        inst.dispatcher.flush()
        inst.event_store.flush()
        snap = inst.dispatcher.metrics_snapshot()
        print(f"accepted {snap['accepted']} events "
              f"({8 * 3} via hosted MQTT + {len(hub_lines)} via AMQP 1.0)")
        # >= : both transports are at-least-once — a lost ack legitimately
        # redelivers, and a duplicate is not a failure
        assert snap["accepted"] >= want, snap

        from sitewhere_tpu.services.common import SearchCriteria

        res = inst.event_store.query(SearchCriteria(page_size=5))
        print(f"store holds {res.total} events; newest:")
        for r in res.results:
            print(f"  device_id={r.device_id} value={r.value:.1f} "
                  f"ts={r.ts_s}")
        state = inst.device_state.get_device_state("edge-3")
        print(f"edge-3 last event ts: {state['last_event_ts_s']}")
        ckpt = os.path.join(tmp, "ckpt", "eventhub-hub.json")
        print(f"eventhub checkpoint: {open(ckpt).read()}")

        # 4. commands flow the other way over the SAME hosted broker
        import queue

        from sitewhere_tpu.commands import (
            CommandDestination,
            JsonCommandEncoder,
            MqttDeliveryProvider,
            TopicParameterExtractor,
        )
        from sitewhere_tpu.schema import EventType

        inst.commands.add_destination(CommandDestination(
            "hosted-mqtt", JsonCommandEncoder(), TopicParameterExtractor(),
            MqttDeliveryProvider("127.0.0.1", broker_port)))
        got: "queue.Queue" = queue.Queue()
        dev = MqttClient("127.0.0.1", broker_port, client_id="edge-0")
        dev.on_message = lambda topic, payload: got.put(payload)
        dev.connect()
        dev.subscribe("sitewhere/command/edge-0", qos=0)
        out = inst.create_command_invocation(
            assignments["edge-0"].token, "reboot")
        cmd = json.loads(got.get(timeout=10))
        print(f"edge-0 received command {cmd['command']!r} "
              f"(invocation {cmd['invocation'][:8]}…)")
        dev.publish("fleet/edge-0/events", json.dumps({
            "deviceToken": "edge-0", "type": "commandResponse",
            "request": {"originatingEventId": out["token"],
                        "response": "rebooted",
                        "eventDate": int(time.time())}}).encode(), qos=1)
        dev.disconnect()
        deadline = time.monotonic() + 10
        correlated = False
        while time.monotonic() < deadline and not correlated:
            inst.dispatcher.flush()
            handle = inst.identity.invocation.lookup(out["token"])
            correlated = handle >= 0 and inst.event_store.query(
                command_id=handle,
                event_type=int(EventType.COMMAND_RESPONSE)).total >= 1
            if not correlated:
                time.sleep(0.05)
        assert correlated, "device ack never correlated to the invocation"
        print("command acknowledged and correlated to its invocation")
    finally:
        inst.stop()
        inst.terminate()
        hub.close()
    print("fleet demo ok")


if __name__ == "__main__":
    main()

"""Instance assembly + bootstrap + end-to-end dispatch loop.

Covers the service-instance-management capability (template bootstrap,
idempotent marker, dataset initializers) and the dispatcher wiring that
replaces the reference's Kafka-connected pipeline services: ingest →
fused step → persistence/state/registration/replay/derived alerts.
"""

import json

import numpy as np
import pytest

from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind
from sitewhere_tpu.instance import Instance, InstanceTemplate
from sitewhere_tpu.runtime.config import Config
from sitewhere_tpu.schema import ComparisonOp, EventType

from time_limit import wait_until


def make_config(tmp_path, **pipeline):
    base = {
        "instance": {"id": "test-instance", "data_dir": str(tmp_path / "data")},
        "pipeline": {
            "width": 64, "registry_capacity": 1024, "mtype_slots": 4,
            "deadline_ms": 5.0, "n_shards": 1, **pipeline,
        },
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
    }
    return Config(base, apply_env=False)


@pytest.fixture()
def instance(tmp_path):
    inst = Instance(make_config(tmp_path))
    inst.start()
    yield inst
    inst.stop()
    inst.terminate()


def seed_device(inst, token="dev-1", mtype=None):
    inst.device_management.create_device_type(token="sensor", name="Sensor")
    inst.device_management.create_device(token=token, device_type="sensor")
    inst.device_management.create_device_assignment(device=token)


def measurement(token, value, ts=1000, mtype="temp"):
    return DecodedRequest(
        kind=RequestKind.MEASUREMENT, device_token=token,
        ts_s=ts, mtype=mtype, value=value,
    )


class TestBootstrap:
    def test_template_applied_once(self, tmp_path):
        ran = []
        template = InstanceTemplate(dataset_initializers=[lambda i: ran.append(1)])
        inst = Instance(make_config(tmp_path), template)
        inst.start()
        assert inst.bootstrapped
        assert ran == [1]
        # default template artifacts
        assert inst.users.get_user("admin").authorities == ["ROLE_ADMIN"]
        assert inst.tenants.get_tenant("default").name == "Default Tenant"
        inst.stop()
        inst.terminate()

        # second process over the same data dir: marker short-circuits
        inst2 = Instance(make_config(tmp_path), template)
        assert inst2.bootstrapped
        inst2.start()
        assert ran == [1]  # initializer did NOT run again
        inst2.stop()
        inst2.terminate()

    def test_login_round_trip(self, instance):
        user = instance.users.authenticate("admin", "password")
        token = instance.tokens.mint(user.username, user.authorities)
        assert instance.tokens.username(token) == "admin"


class TestDispatchLoop:
    def test_ingest_to_store_and_state(self, instance):
        seed_device(instance)
        for i in range(10):
            instance.dispatcher.ingest(measurement("dev-1", 20.0 + i, ts=1000 + i))
        instance.dispatcher.flush()
        snap = instance.dispatcher.metrics_snapshot()
        assert snap["processed"] == 10
        assert snap["accepted"] == 10
        # state merged
        state = instance.device_state.get_device_state("dev-1")
        assert state["last_event_ts_s"] == 1009
        # events persisted
        instance.event_store.flush()
        assert instance.event_store.total_events == 10

    def test_threshold_rule_fires_derived_alert(self, instance):
        seed_device(instance)
        instance.rules.create_rule(
            mtype="temp", op=ComparisonOp.GT, threshold=90.0, alert_type="overheat",
        )
        instance.dispatcher.ingest(measurement("dev-1", 95.0, ts=2000))
        instance.dispatcher.flush()
        instance.dispatcher.flush()  # second flush carries the derived alert
        snap = instance.dispatcher.metrics_snapshot()
        assert snap["threshold_alerts"] == 1
        assert snap["derived_alerts"] == 1
        instance.event_store.flush()
        # stored: the measurement + the derived ALERT event
        alerts = instance.event_store.query(event_type=int(EventType.ALERT))
        assert alerts.total == 1

    def test_auto_registration_and_replay(self, tmp_path):
        cfg = make_config(tmp_path)
        inst = Instance(cfg)
        inst.template.tenants[0]["token"] = "default"
        inst.start()
        inst.device_management.create_device_type(token="sensor", name="Sensor")
        inst.registration.default_device_type = "sensor"
        # unknown device arrives with a journaled payload
        payload = json.dumps({
            "deviceToken": "ghost-1", "type": "measurement",
            "request": {"name": "temp", "value": 7.0, "ts": 3000},
        }).encode()
        from sitewhere_tpu.ingest.decoders import JsonDecoder

        req = JsonDecoder()(payload)[0]
        inst.dispatcher.ingest(req, payload)
        inst.dispatcher.flush()  # step 1: dead-letter + register + replay queue
        inst.dispatcher.flush()  # step 2: replayed row accepted
        snap = inst.dispatcher.metrics_snapshot()
        assert snap["unregistered"] == 1
        assert snap["replayed"] == 1
        assert inst.registration.registered == 1
        # device now exists with an active assignment; replay accepted
        assert inst.device_management.get_device("ghost-1") is not None
        assert snap["accepted"] == 1
        inst.stop()
        inst.terminate()

    def test_unknown_tenant_events_rejected(self, instance):
        """Events resolve tenant 'default'; a device owned by another tenant
        dead-letters (tenant isolation)."""
        seed_device(instance)
        # move device to a different tenant in the registry mirror
        dev_id = instance.identity.device.lookup("dev-1")
        other = instance.identity.tenant.mint("other-tenant")
        row = {"active": True, "tenant_id": other, "device_type_id": 0,
               "assignment_id": dev_id, "assignment_status": 1,
               "area_id": -1, "customer_id": -1, "asset_id": -1}
        instance.mirror.set_device_row(dev_id, **row)
        instance.dispatcher.ingest(measurement("dev-1", 1.0))
        instance.dispatcher.flush()
        snap = instance.dispatcher.metrics_snapshot()
        assert snap["unregistered"] == 1 and snap["accepted"] == 0

    def test_presence_changes_reinjected(self, instance):
        seed_device(instance)
        instance.dispatcher.ingest(measurement("dev-1", 1.0, ts=1000))
        instance.dispatcher.flush()
        batch = instance.device_state.apply_presence_sweep(
            now_s=1000 + 3600, missing_after_s=1800
        )
        assert batch is not None
        instance._on_presence_changes(batch)
        instance.dispatcher.flush()
        instance.event_store.flush()
        changes = instance.event_store.query(
            event_type=int(EventType.STATE_CHANGE)
        )
        assert changes.total == 1
        # the re-injected STATE_CHANGE must NOT make the device look alive
        dev_id = instance.identity.device.lookup("dev-1")
        assert instance.device_state.missing_device_ids() == [dev_id]
        assert (instance.device_state.get_device_state("dev-1")
                ["last_event_ts_s"] == 1000)

    def test_deep_inflight_window_equivalent(self, tmp_path):
        """inflight_depth=8 (the TPU default — dispatch-latency hiding)
        must produce identical store/state/metrics results to the CPU
        default of 1, and flush() must drain the whole window."""
        inst = Instance(make_config(tmp_path, inflight_depth=8))
        inst.start()
        try:
            assert inst.dispatcher.inflight_depth == 8
            seed_device(inst)
            # several full plans (width 64) so the window actually fills
            for i in range(300):
                inst.dispatcher.ingest(
                    measurement("dev-1", float(i), ts=1000 + i))
            inst.dispatcher.flush()
            snap = inst.dispatcher.metrics_snapshot()
            assert snap["processed"] == 300
            assert snap["accepted"] == 300
            assert len(inst.dispatcher._inflight) == 0
            state = inst.device_state.get_device_state("dev-1")
            assert state["last_event_ts_s"] == 1299
            inst.event_store.flush()
            assert inst.event_store.total_events == 300
        finally:
            inst.stop()
            inst.terminate()

    def test_background_loop_respects_deadline(self, tmp_path):
        inst = Instance(make_config(tmp_path, deadline_ms=10.0))
        inst.start()
        seed_device(inst)
        inst.dispatcher.ingest(measurement("dev-1", 5.0))
        # background loop must emit within a few deadlines without flush
        assert wait_until(
            lambda: inst.dispatcher.metrics_snapshot()["accepted"] >= 1)
        assert inst.dispatcher.metrics_snapshot()["accepted"] == 1
        inst.stop()
        inst.terminate()

    def test_topology_snapshot(self, instance):
        seed_device(instance)
        instance.dispatcher.ingest(measurement("dev-1", 1.0))
        instance.dispatcher.flush()
        topo = instance.topology()
        assert topo["instance"] == "test-instance"
        assert topo["bootstrapped"]
        assert topo["devices"] == 1
        names = [c["name"] for c in topo["components"]["children"]]
        assert "pipeline-dispatcher" in names and "event-store" in names


class TestCommandDelivery:
    def test_pipeline_invocation_reaches_destination(self, tmp_path):
        """COMMAND_INVOCATION events from ingest resolve their journaled
        payload and deliver through the command processor (reference:
        enriched-command-invocations -> command-delivery, SURVEY.md 3.4)."""
        from sitewhere_tpu.commands.destinations import (
            CallbackDeliveryProvider,
            CommandDestination,
        )
        from sitewhere_tpu.commands.encoders import JsonCommandEncoder

        inst = Instance(make_config(tmp_path))
        inst.start()
        seed_device(inst)
        inst.device_management.create_device_command(
            "sensor", token="ping", name="ping")
        delivered = []
        inst.commands.add_destination(CommandDestination(
            destination_id="test",
            encoder=JsonCommandEncoder(),
            extractor=lambda ex: {},
            provider=CallbackDeliveryProvider(
                lambda ex, payload, params: delivered.append(ex)),
        ))

        payload = json.dumps({
            "deviceToken": "dev-1", "type": "commandinvocation",
            "request": {"commandToken": "ping"},
        }).encode()
        from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind

        req = DecodedRequest(
            kind=RequestKind.COMMAND_INVOCATION, device_token="dev-1",
            ts_s=1000)
        inst.dispatcher.ingest(req, payload)
        inst.dispatcher.flush()
        assert inst.dispatcher.metrics_snapshot()["commands"] == 1
        assert len(delivered) == 1
        assert delivered[0].invocation.command_token == "ping"
        inst.stop()
        inst.terminate()

    def test_unresolvable_invocation_dead_letters(self, tmp_path):
        inst = Instance(make_config(tmp_path))
        inst.start()
        seed_device(inst)
        from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind

        # no journaled payload -> no command spec -> dead letter
        req = DecodedRequest(
            kind=RequestKind.COMMAND_INVOCATION, device_token="dev-1",
            ts_s=1000)
        before = inst.dead_letters.end_offset
        inst.dispatcher.ingest(req)
        inst.dispatcher.flush()
        assert inst.dead_letters.end_offset == before + 1
        record = json.loads(inst.dead_letters.read_one(before))
        assert record["kind"] == "undeliverable-invocation"
        inst.stop()
        inst.terminate()


class TestBatchIngest:
    def test_source_batch_payload_journals_once_and_processes_all(self, instance):
        """A multi-event wire payload forwards through ingest_many: one
        journal record, every event processed (batch-decoder fast path)."""
        from sitewhere_tpu.ingest.decoders import JsonBatchDecoder
        from sitewhere_tpu.ingest.sources import InboundEventSource

        seed_device(instance)
        src = instance.add_source(InboundEventSource(
            source_id="batch", receivers=[], decoder=JsonBatchDecoder()))
        payload = json.dumps({
            "deviceToken": "dev-1",
            "events": [
                {"type": "measurement", "name": "temp", "value": float(v),
                 "ts": 2000 + v}
                for v in range(5)
            ],
        }).encode()
        before = instance.ingest_journal.end_offset
        src.on_encoded_payload(payload)
        # intake is asynchronous with the decode pool attached: drain it
        # before flushing so the forward (journal + batch) has happened
        if instance.decode_pool is not None:
            assert instance.decode_pool.flush()
        instance.dispatcher.flush()
        assert instance.ingest_journal.end_offset == before + 1
        snap = instance.dispatcher.metrics_snapshot()
        assert snap["accepted"] >= 5

    def test_ingest_many_rejects_host_plane_before_journaling(self, instance):
        seed_device(instance)
        before = instance.ingest_journal.end_offset
        bad = DecodedRequest(
            kind=RequestKind.STREAM_DATA, device_token="dev-1", ts_s=1000)
        with pytest.raises(ValueError):
            instance.dispatcher.ingest_many(
                [measurement("dev-1", 1.0), bad], b'{"x":1}')
        assert instance.ingest_journal.end_offset == before

"""Overload control: watermark state machine, priority admission,
protocol-native backpressure, and the degradation ladder.

The controller itself is verified deterministically (injected clock, no
sleeps): hysteresis keeps the state while signals sit between the exit
and enter watermarks, and de-escalation lands within exactly ONE
cooldown of the load dropping.  The integration tests force states
through the ops hook and prove the layer contracts: CRITICAL events
always reach seal, telemetry sheds are counted + dead-lettered +
signalled natively (HTTP 429/Retry-After, CoAP 5.03/Max-Age, withheld
PUBACK, unacked STOMP/AMQP deliveries), and shed payloads are
replayable through the dead-letter requeue path.
"""

import json
import socket
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.overload import (
    OverloadController,
    OverloadShed,
    OverloadSignals,
    OverloadState,
    PriorityClass,
    TokenBucket,
    Watermarks,
    classify_event_type,
)

from time_limit import wait_until


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _controller(clock, **kw):
    kw.setdefault("cooldown_s", 2.0)
    kw.setdefault("metrics", MetricsRegistry())
    return OverloadController(clock=clock, **kw)


# ---------------------------------------------------------------------------
# the state machine: escalation, hysteresis, cooldown — deterministic
# ---------------------------------------------------------------------------

class TestStateMachine:
    def test_escalates_immediately_on_enter_watermark(self):
        clock = FakeClock()
        c = _controller(clock)
        assert c.state == OverloadState.NORMAL
        assert c.observe(OverloadSignals(batcher_backlog=1.5)) \
            == OverloadState.DEGRADED
        # jumps straight to the justified level, no rung-by-rung climb
        assert c.observe(OverloadSignals(seal_lag_s=3.0)) \
            == OverloadState.EMERGENCY
        assert c.transitions == 2
        assert c.last_driver == "seal_lag_s"

    def test_hysteresis_holds_state_between_exit_and_enter(self):
        clock = FakeClock()
        c = _controller(clock, hysteresis=0.7)
        c.observe(OverloadSignals(batcher_backlog=4.5))
        assert c.state == OverloadState.SHEDDING
        # 3.0 is below the SHEDDING enter (4.0) but above its exit
        # (4.0 * 0.7 = 2.8): the state must HOLD however long it lasts
        for _ in range(10):
            clock.t += 10.0
            assert c.observe(OverloadSignals(batcher_backlog=3.0)) \
                == OverloadState.SHEDDING

    def test_deescalates_within_one_cooldown_of_load_drop(self):
        clock = FakeClock()
        c = _controller(clock, cooldown_s=2.0)
        c.observe(OverloadSignals(egress_inflight=2.0))
        assert c.state == OverloadState.EMERGENCY
        calm = OverloadSignals()
        clock.t += 0.5
        assert c.observe(calm) == OverloadState.EMERGENCY  # cooldown starts
        clock.t += 1.9
        assert c.observe(calm) == OverloadState.EMERGENCY  # 1.9s < 2.0s
        clock.t += 0.2
        # one cooldown after the drop: straight to NORMAL, not one rung
        assert c.observe(calm) == OverloadState.NORMAL

    def test_spike_during_cooldown_restarts_it(self):
        clock = FakeClock()
        c = _controller(clock, cooldown_s=2.0)
        c.observe(OverloadSignals(decode_backlog=0.9))
        assert c.state == OverloadState.SHEDDING
        clock.t += 1.9
        c.observe(OverloadSignals())          # almost recovered...
        c.observe(OverloadSignals(decode_backlog=0.9))  # ...spike
        clock.t += 1.9
        # the spike restarted the cooldown: 1.9s below is not enough
        assert c.observe(OverloadSignals()) == OverloadState.SHEDDING
        clock.t += 2.1
        assert c.observe(OverloadSignals()) == OverloadState.NORMAL

    def test_confirm_samples_rejects_one_sample_spikes(self):
        """A single slow plan pinning a last-value gauge (a jit
        compile, one disk stall) is a spike, not sustained overload:
        with confirm_samples=2 the enter watermark must hold for two
        consecutive samples before the ladder moves."""
        clock = FakeClock()
        c = _controller(clock, confirm_samples=2)
        hot = OverloadSignals(seal_lag_s=3.0)
        assert c.observe(hot) == OverloadState.NORMAL   # 1st: pending
        assert c.observe(OverloadSignals()) == OverloadState.NORMAL
        assert c.observe(hot) == OverloadState.NORMAL   # count restarted
        assert c.observe(hot) == OverloadState.EMERGENCY  # confirmed
        # a streak whose level varies escalates to the MINIMUM level it
        # sustained — every sample justified at least DEGRADED
        c2 = _controller(clock, confirm_samples=2)
        c2.observe(OverloadSignals(seal_lag_s=3.0))     # EMERGENCY-level
        assert c2.observe(OverloadSignals(seal_lag_s=0.2)) \
            == OverloadState.DEGRADED                   # confirmed at min

    def test_flapping_signal_still_escalates_to_sustained_level(self):
        """Regression: a noisy signal straddling one watermark boundary
        (levels 1,2,1,2,…) used to restart the confirmation count on
        every sample and NEVER escalate, leaving admission off under
        genuine sustained overload."""
        c = _controller(FakeClock(), confirm_samples=3)
        for i in range(3):
            level = c.observe(OverloadSignals(
                seal_lag_s=0.55 if i % 2 else 0.12))
        assert level == OverloadState.DEGRADED   # min sustained level

    def test_pending_escalation_restarts_the_cooldown(self):
        """Regression: an above-watermark sample that merely ARMED the
        escalation confirmation (without transitioning) must still
        restart the de-escalation cooldown — the contract is cooldown_s
        of CONTINUOUS calm."""
        clock = FakeClock()
        c = _controller(clock, cooldown_s=2.0, confirm_samples=2)
        c.force(OverloadState.DEGRADED)
        c.observe(OverloadSignals())              # calm: cooldown starts
        clock.t += 1.95
        # one spike above the SHEDDING enter watermark — not confirmed,
        # no transition, but it breaks the continuous calm
        c.observe(OverloadSignals(seal_lag_s=0.55))
        clock.t += 0.05
        assert c.observe(OverloadSignals()) == OverloadState.DEGRADED
        clock.t += 2.1   # a FULL cooldown after the spike
        assert c.observe(OverloadSignals()) == OverloadState.NORMAL

    def test_transition_metrics_and_snapshot(self):
        clock = FakeClock()
        reg = MetricsRegistry()
        c = _controller(clock, metrics=reg)
        seen = []
        c.on_transition(lambda old, new, sig: seen.append((old, new)))
        c.observe(OverloadSignals(fsync_latency_s=0.3))
        assert seen == [(OverloadState.NORMAL, OverloadState.SHEDDING)]
        assert reg.gauge("overload.state").value == 2
        assert reg.counter("overload.transitions.to_shedding").value == 1
        snap = c.snapshot()
        assert snap["state"] == "SHEDDING"
        assert snap["driver"] == "fsync_latency_s"
        assert snap["signals"]["fsync_latency_s"] == 0.3

    def test_watermark_overrides_validate(self):
        w = Watermarks().replace({"batcher_backlog": [0.1, 0.2, 0.3]})
        assert w.batcher_backlog == (0.1, 0.2, 0.3)
        with pytest.raises(ValueError):
            Watermarks().replace({"nope": [1, 2, 3]})
        with pytest.raises(ValueError):
            Watermarks().replace({"seal_lag_s": [3, 2, 1]})


# ---------------------------------------------------------------------------
# admission: priority classes + token buckets
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_classification(self):
        from sitewhere_tpu.schema import EventType

        assert classify_event_type(EventType.MEASUREMENT) \
            == PriorityClass.TELEMETRY
        assert classify_event_type(EventType.LOCATION) \
            == PriorityClass.TELEMETRY
        assert classify_event_type(EventType.ALERT) == PriorityClass.CRITICAL
        assert classify_event_type(EventType.COMMAND_RESPONSE) \
            == PriorityClass.CRITICAL
        assert classify_event_type(EventType.COMMAND_INVOCATION) \
            == PriorityClass.COMMAND
        assert classify_event_type(99) == PriorityClass.COMMAND

    def test_critical_never_shed_even_in_emergency(self):
        clock = FakeClock()
        c = _controller(clock)
        c.force(OverloadState.EMERGENCY)
        for _ in range(100):
            assert c.admit(PriorityClass.CRITICAL)
        assert c.shed_total == 0

    def test_telemetry_rate_limited_in_degraded(self):
        clock = FakeClock()
        c = _controller(clock, degraded_telemetry_rate_per_s=10.0,
                        degraded_telemetry_burst=5.0)
        c.force(OverloadState.DEGRADED)
        assert c.admit(PriorityClass.TELEMETRY, n=5)   # burst
        assert not c.admit(PriorityClass.TELEMETRY, n=5)  # bucket empty
        clock.t += 0.5   # refill 5 tokens at 10/s
        assert c.admit(PriorityClass.TELEMETRY, n=5)

    def test_telemetry_refused_outright_in_shedding(self):
        c = _controller(FakeClock())
        c.force(OverloadState.SHEDDING)
        assert not c.admit(PriorityClass.TELEMETRY)
        assert c.admit(PriorityClass.COMMAND)   # bucket still has burst
        c.force(OverloadState.EMERGENCY)
        assert not c.admit(PriorityClass.COMMAND)

    def test_per_tenant_buckets_isolate(self):
        clock = FakeClock()
        c = _controller(clock, degraded_telemetry_rate_per_s=1.0,
                        degraded_telemetry_burst=2.0)
        c.force(OverloadState.DEGRADED)
        assert c.admit(PriorityClass.TELEMETRY, tenant="a", n=2)
        assert not c.admit(PriorityClass.TELEMETRY, tenant="a", n=1)
        # tenant b's bucket is untouched by a's exhaustion
        assert c.admit(PriorityClass.TELEMETRY, tenant="b", n=2)

    def test_shed_counters_per_class_and_tenant(self):
        reg = MetricsRegistry()
        c = _controller(FakeClock(), metrics=reg)
        c.force(OverloadState.SHEDDING)
        c.admit(PriorityClass.TELEMETRY, tenant="acme", n=7)
        assert reg.counter("overload.shed.telemetry").value == 7
        assert reg.counter("tenant.shed.acme").value == 7
        assert c.shed_total == 7

    def test_buckets_reset_on_return_to_normal(self):
        clock = FakeClock()
        c = _controller(clock, degraded_telemetry_rate_per_s=1.0,
                        degraded_telemetry_burst=1.0)
        c.force(OverloadState.DEGRADED)
        assert c.admit(PriorityClass.TELEMETRY)
        assert not c.admit(PriorityClass.TELEMETRY)
        c.force(OverloadState.NORMAL)
        c.force(OverloadState.DEGRADED)
        assert c.admit(PriorityClass.TELEMETRY)   # fresh burst

    def test_retry_after_scales_with_severity(self):
        c = _controller(FakeClock(), retry_after_s=2.0)
        c.force(OverloadState.DEGRADED)
        assert c.retry_after() == 2.0
        c.force(OverloadState.EMERGENCY)
        assert c.retry_after() == 6.0

    def test_token_bucket_refill(self):
        clock = FakeClock()
        b = TokenBucket(rate_per_s=2.0, burst=4.0, clock=clock)
        assert b.try_take(4)
        assert not b.try_take(1)
        clock.t += 1.0
        assert b.try_take(2)


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

class TestDegradationLadder:
    def test_optional_off_from_degraded(self):
        c = _controller(FakeClock())
        assert c.allow_optional("labels")
        c.force(OverloadState.DEGRADED)
        assert not c.allow_optional("labels")

    def test_fanout_sheds_non_priority_from_shedding(self):
        c = _controller(FakeClock())
        c.force(OverloadState.DEGRADED)
        assert c.allow_fanout(priority=False)   # DEGRADED keeps fan-out
        c.force(OverloadState.SHEDDING)
        assert not c.allow_fanout(priority=False)
        assert c.allow_fanout(priority=True)    # alert notifiers flow

    def test_outbound_manager_sheds_only_non_priority(self):
        from sitewhere_tpu.outbound.connectors import CallbackConnector
        from sitewhere_tpu.outbound.manager import OutboundConnectorsManager

        c = _controller(FakeClock())
        bulk_got, alert_got = [], []
        bulk = CallbackConnector(
            "bulk-indexer", lambda cols, m: bulk_got.append(int(m.sum())))
        alerts = CallbackConnector(
            "alert-notifier", lambda cols, m: alert_got.append(int(m.sum())),
            priority=True)
        mgr = OutboundConnectorsManager([bulk, alerts], overload=c)
        mgr.start()
        try:
            cols = {"device_id": np.arange(4, dtype=np.int32)}
            mask = np.ones(4, bool)
            c.force(OverloadState.SHEDDING)
            mgr.submit(cols, mask)
            mgr.drain(5.0)
            assert alert_got == [4]
            assert bulk_got == []
            assert mgr._workers["bulk-indexer"].overload_shed == 1
            c.force(OverloadState.NORMAL)
            mgr.submit(cols, mask)
            mgr.drain(5.0)
            assert bulk_got == [4]
        finally:
            mgr.stop()

    def test_label_generation_refuses_under_load(self):
        from sitewhere_tpu.labels.manager import LabelGeneratorManager
        from sitewhere_tpu.services.common import ServiceUnavailable

        c = _controller(FakeClock())
        mgr = LabelGeneratorManager()
        mgr.load_gate = c.allow_optional
        assert mgr.generate_png("default", "device", "d-1")
        c.force(OverloadState.DEGRADED)
        with pytest.raises(ServiceUnavailable):
            mgr.generate_png("default", "device", "d-1")
        assert mgr.refused_under_load == 1
        c.force(OverloadState.NORMAL)
        assert mgr.generate_png("default", "device", "d-1")

    def test_outbound_drain_wakes_without_polling(self):
        """Satellite regression: drain used to spin on unfinished_tasks
        at 5ms; it now blocks on the queue's all_tasks_done condition —
        a finished batch wakes it immediately and an unmet deadline
        returns on time."""
        from sitewhere_tpu.outbound.connectors import CallbackConnector
        from sitewhere_tpu.outbound.manager import OutboundConnectorsManager

        release = []

        def slow(cols, mask):
            wait_until(lambda: release)

        mgr = OutboundConnectorsManager([CallbackConnector("slow", slow)])
        mgr.start()
        try:
            cols = {"device_id": np.arange(2, dtype=np.int32)}
            mgr.submit(cols, np.ones(2, bool))
            t0 = time.monotonic()
            mgr.drain(timeout=0.2)           # deadline honored...
            assert time.monotonic() - t0 < 1.0
            release.append(True)
            mgr.drain(timeout=5.0)           # ...and completion wakes it
            assert mgr._workers["slow"].q.unfinished_tasks == 0
        finally:
            mgr.stop()


# ---------------------------------------------------------------------------
# dispatcher admission: dead-letter audit + replayability
# ---------------------------------------------------------------------------

def _instance_config(tmp_path, overload=None, **pipeline):
    from sitewhere_tpu.runtime.config import Config

    return Config({
        "instance": {"id": "ov-inst", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 128,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1,
                     **pipeline},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        # a state the test forces holds until the test moves it: the
        # loop's tick de-escalates only after an hour of calm
        "overload": {"enabled": True, "cooldown_s": 3600.0,
                     **(overload or {})},
    }, apply_env=False)


def _seed_device(inst, token="d-0"):
    inst.device_management.create_device_type(token="sensor", name="Sensor")
    inst.device_management.create_device(token=token, device_type="sensor")
    inst.device_management.create_device_assignment(device=token)


def _measurement(token, value, ts=1_753_800_000):
    return json.dumps({
        "deviceToken": token, "type": "Measurement",
        "request": {"name": "temp", "value": value, "eventDate": ts},
    })


def _alert(token, ts=1_753_800_000):
    return json.dumps({
        "deviceToken": token, "type": "Alert",
        "request": {"type": "overheat", "level": "warning",
                    "message": "hot", "eventDate": ts},
    })


def _dead_letters(inst, kind):
    return [d for d in inst.list_dead_letters(limit=100)
            if d.get("kind") == kind]


class TestDispatcherAdmission:
    def test_full_shed_raises_dead_letters_and_skips_journal(self, tmp_path):
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_device(inst)
            inst.overload.force(OverloadState.SHEDDING)
            payload = "\n".join(
                [_measurement("d-0", i) for i in range(3)]).encode()
            with pytest.raises(OverloadShed) as exc:
                inst.dispatcher.ingest_wire_lines(payload, "src-1")
            assert exc.value.retry_after_s > 0
            # shed ≠ journaled: the offset space holds only admitted work
            assert inst.ingest_journal.end_offset == 0
            letters = _dead_letters(inst, "intake-shed")
            assert len(letters) == 1
            assert letters[0]["classes"] == {"telemetry": 3}
            assert letters[0]["state"] == "SHEDDING"
            assert letters[0]["source"] == "src-1"
            assert bytes.fromhex(letters[0]["payload"]) == payload
        finally:
            inst.stop()
            inst.terminate()

    def test_partial_shed_admits_critical_rows_to_seal(self, tmp_path):
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_device(inst)
            inst.overload.force(OverloadState.SHEDDING)
            payload = "\n".join([
                _measurement("d-0", 1.0),
                _alert("d-0"),
                _measurement("d-0", 2.0),
            ]).encode()
            n = inst.dispatcher.ingest_wire_lines(payload, "src-1")
            assert n == 1   # the alert row
            inst.dispatcher.flush()
            inst.event_store.flush()
            # CRITICAL reached seal even while SHEDDING
            assert inst.event_store.total_events == 1
            assert inst.dispatcher.totals["accepted"] == 1
            assert inst.metrics.counter(
                "overload.shed.telemetry").value == 2
            assert inst.metrics.counter(
                "overload.shed.critical").value == 0
            letters = _dead_letters(inst, "intake-shed")
            assert letters[0]["classes"] == {"telemetry": 2}
        finally:
            inst.stop()
            inst.terminate()

    def test_scalar_ingest_many_partial_shed(self, tmp_path):
        from sitewhere_tpu.ingest.decoders import JsonLinesDecoder
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_device(inst)
            inst.overload.force(OverloadState.EMERGENCY)
            decoder = JsonLinesDecoder()
            mixed = decoder("\n".join(
                [_measurement("d-0", 1.0), _alert("d-0")]).encode())
            inst.dispatcher.ingest_many(mixed, b"raw", source_id="s")
            inst.dispatcher.flush()
            inst.event_store.flush()
            assert inst.event_store.total_events == 1
            with pytest.raises(OverloadShed):
                inst.dispatcher.ingest_many(
                    decoder(_measurement("d-0", 3.0).encode()), b"raw2",
                    source_id="s")
        finally:
            inst.stop()
            inst.terminate()

    def test_journal_replay_bypasses_admission(self, tmp_path):
        """Already-journaled work is NEVER shed: replay is how the
        fail-closed durability contract recovers, and shedding it would
        turn an overload into data loss."""
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_device(inst)
            # a journaled-but-unprocessed record, as a crash leaves it
            inst.ingest_journal.append(_measurement("d-0", 7.0).encode())
            inst.overload.force(OverloadState.EMERGENCY)
            replayed = inst.dispatcher.replay_journal(upto=1)
            assert replayed == 1   # telemetry replayed even in EMERGENCY
            inst.event_store.flush()
            assert inst.event_store.total_events == 1
        finally:
            inst.stop()
            inst.terminate()

    def test_shed_payload_is_requeueable_after_recovery(self, tmp_path):
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_device(inst)
            inst.overload.force(OverloadState.SHEDDING)
            payload = _measurement("d-0", 9.0).encode()
            with pytest.raises(OverloadShed):
                inst.dispatcher.ingest_wire_lines(payload)
            offset = _dead_letters(inst, "intake-shed")[0]["offset"]
            # still overloaded: the requeue is refused, not re-shed
            refused = inst.requeue_dead_letter(offset)
            assert refused["requeued"] is False
            # recovered: the audited payload replays into the pipeline
            inst.overload.force(OverloadState.NORMAL)
            result = inst.requeue_dead_letter(offset)
            assert result["requeued"] is True and result["rows"] == 1
            inst.dispatcher.flush()
            inst.event_store.flush()
            assert inst.event_store.total_events == 1
        finally:
            inst.stop()
            inst.terminate()


# ---------------------------------------------------------------------------
# protocol-native backpressure: shed ≠ silent drop, per transport
# ---------------------------------------------------------------------------

class TestProtocolBackpressure:
    def test_http_answers_429_with_retry_after(self, tmp_path):
        from sitewhere_tpu.ingest.decoders import JsonLinesDecoder
        from sitewhere_tpu.ingest.sources import (
            HttpReceiver,
            InboundEventSource,
        )
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(
            tmp_path, overload={"retry_after_s": 3.0}))
        rx = HttpReceiver(port=0)
        src = InboundEventSource("http-src", [rx], JsonLinesDecoder())
        inst.add_source(src)
        inst.start()
        try:
            _seed_device(inst)
            url = f"http://127.0.0.1:{rx.port}/events"

            def post(body):
                return urllib.request.urlopen(urllib.request.Request(
                    url, data=body, method="POST"), timeout=10)

            assert post(_measurement("d-0", 1.0).encode()).status == 202
            inst.overload.force(OverloadState.SHEDDING)
            with pytest.raises(urllib.error.HTTPError) as exc:
                post(_measurement("d-0", 2.0).encode())
            assert exc.value.code == 429
            assert exc.value.headers["Retry-After"] == "6"  # 3.0 * state 2
            # CRITICAL still flows over the same connection path
            assert post(_alert("d-0").encode()).status == 202
            assert src.shed_count == 1
            assert rx.sheds == 1
            inst.overload.force(OverloadState.NORMAL)
            assert post(_measurement("d-0", 3.0).encode()).status == 202
        finally:
            inst.stop()
            inst.terminate()

    def test_coap_answers_503_with_max_age(self, tmp_path):
        from sitewhere_tpu.ingest.coap import (
            ACK,
            OPT_MAX_AGE,
            UNAVAILABLE_503,
            CHANGED_204,
            CoapServerReceiver,
            encode_post,
            parse_message,
        )
        from sitewhere_tpu.ingest.decoders import JsonLinesDecoder
        from sitewhere_tpu.ingest.sources import InboundEventSource
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(
            tmp_path, overload={"retry_after_s": 2.0}))
        rx = CoapServerReceiver(port=0)
        src = InboundEventSource("coap-src", [rx], JsonLinesDecoder())
        inst.add_source(src)
        inst.start()
        try:
            _seed_device(inst)
            client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            client.settimeout(5.0)

            def post(body, mid):
                client.sendto(
                    encode_post("/events", body, message_id=mid),
                    ("127.0.0.1", rx.port))
                data, _ = client.recvfrom(65536)
                return parse_message(data)

            ok = post(_measurement("d-0", 1.0).encode(), 1)
            assert (ok.mtype, ok.code) == (ACK, CHANGED_204)
            inst.overload.force(OverloadState.SHEDDING)
            shed = post(_measurement("d-0", 2.0).encode(), 2)
            assert (shed.mtype, shed.code) == (ACK, UNAVAILABLE_503)
            max_age = shed.option(OPT_MAX_AGE)
            assert int.from_bytes(max_age, "big") == 4  # 2.0 * state 2
            # the alert POST still gets its 2.04 while SHEDDING
            hot = post(_alert("d-0").encode(), 3)
            assert (hot.mtype, hot.code) == (ACK, CHANGED_204)
            client.close()
        finally:
            inst.stop()
            inst.terminate()

    def test_mqtt_broker_withholds_puback_and_keeps_session(self, tmp_path):
        from sitewhere_tpu.ingest.decoders import JsonLinesDecoder
        from sitewhere_tpu.ingest.mqtt import MqttClient
        from sitewhere_tpu.ingest.mqtt_broker import MqttBrokerReceiver
        from sitewhere_tpu.ingest.sources import InboundEventSource
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        rx = MqttBrokerReceiver(topic_filter="sitewhere/input/#")
        src = InboundEventSource("mqtt-src", [rx], JsonLinesDecoder())
        inst.add_source(src)
        inst.start()
        try:
            _seed_device(inst)
            dev = MqttClient("127.0.0.1", rx.port, client_id="dev-ov")
            dev.connect()
            inst.overload.force(OverloadState.SHEDDING)
            dev.publish("sitewhere/input/dev-ov",
                        _measurement("d-0", 1.0).encode(), qos=1)
            # the PUBACK is WITHHELD (the device's redelivery cue)...
            assert not dev.drain_publishes(timeout=1.0)
            assert wait_until(lambda: rx.broker.sheds == 1)
            # ...but the session survives: shedding is flow control
            assert rx.broker.session_count == 1
            assert rx.broker.tap_failures == 0   # shed ≠ fault
            inst.overload.force(OverloadState.NORMAL)
            # device-side at-least-once: reconnect and redeliver (the
            # withheld PUBACK is what makes the device do this)
            dev2 = MqttClient("127.0.0.1", rx.port, client_id="dev-ov")
            dev2.connect()
            dev2.publish("sitewhere/input/dev-ov",
                         _measurement("d-0", 1.0).encode(), qos=1)
            assert dev2.drain_publishes(timeout=10.0)
            dev2.disconnect()
        finally:
            inst.stop()
            inst.terminate()

    def test_stomp_leaves_message_unacked(self):
        from sitewhere_tpu.ingest.stomp import StompReceiver

        from test_stomp_http import MiniBroker

        broker = MiniBroker()
        got = []
        shedding = [True]

        def sink(payload):
            if shedding[0]:
                raise OverloadShed(PriorityClass.TELEMETRY,
                                   OverloadState.SHEDDING, 1.0)
            got.append(payload)

        rx = StompReceiver("127.0.0.1", broker.port,
                           destination="/queue/q", heartbeat_ms=0,
                           reconnect_delay_s=0.05)
        rx.sink = sink
        rx.start()
        try:
            assert wait_until(lambda: broker.subscribes)
            broker.push("m-1", b"ev-1")
            assert wait_until(lambda: rx.sheds == 1)
            time.sleep(0.05)
            assert broker.acks == []       # unacked → broker redelivers
            assert rx.emit_errors == 0     # shed is not a fault
            shedding[0] = False
            broker.push("m-1", b"ev-1")    # broker-side redelivery
            assert wait_until(lambda: got == [b"ev-1"])
            assert wait_until(lambda: broker.acks == ["m-1"])
        finally:
            rx.stop()
            broker.close()

    def test_amqp_sheds_with_paced_nack_requeue(self):
        """A shed delivery is nacked with requeue after a pacing pause
        (never acked, never logged as a fault): leaving it unacked
        would strand it in the prefetch window of a heartbeat-healthy
        session and wedge the consumer forever.  The broker redelivers
        the requeued message and it lands once admission reopens."""
        from sitewhere_tpu.ingest.amqp import AmqpReceiver

        from test_amqp import MiniAmqpBroker

        broker = MiniAmqpBroker()
        got = []
        shedding = [True]

        def sink(payload):
            if shedding[0]:
                raise OverloadShed(PriorityClass.TELEMETRY,
                                   OverloadState.SHEDDING, 1.0)
            got.append(payload)

        rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1")
        rx.sink = sink
        rx.start()
        try:
            assert wait_until(lambda: broker.sessions == 1)
            broker.push(b"telemetry-1")
            assert wait_until(lambda: rx.sheds >= 1)
            # nacked with the requeue bit — broker-native redelivery
            assert wait_until(lambda: len(broker.nacks) >= 1)
            assert broker.nacks[0][1] == 0x02
            assert rx.emit_errors == 0     # shed is not a fault
            assert rx.nacked == 0          # ...and not a sink failure
            shedding[0] = False            # overload clears
            # the requeued redelivery lands and acks
            assert wait_until(lambda: b"telemetry-1" in got)
            assert wait_until(lambda: len(broker.acks) >= 1)
        finally:
            rx.stop()
            broker.close()

    def test_ackless_receivers_swallow_shed(self):
        """UDP (and TCP/WS/poll) have no ack channel: a shed must NOT
        crash the supervised loop — it was already counted +
        dead-lettered at the admission edge."""
        from sitewhere_tpu.ingest.sources import UdpReceiver

        rx = UdpReceiver(port=0)
        rx.sink = lambda payload: (_ for _ in ()).throw(
            OverloadShed(PriorityClass.TELEMETRY, OverloadState.SHEDDING))
        rx.start()
        try:
            client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            client.sendto(b"telemetry", ("127.0.0.1", rx.port))
            assert wait_until(lambda: rx.sheds == 1)
            assert rx.supervisor.restarts == 0   # not treated as a crash
            client.close()
        finally:
            rx.stop()


# ---------------------------------------------------------------------------
# tools/overload_bench.py smoke — the tool is how a regression in the
# goodput curve (collapse instead of graceful shedding) localizes
# ---------------------------------------------------------------------------

class TestOverloadBenchSmoke:
    def test_tool_reports_curve_and_never_sheds_alerts(self):
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "tools", "overload_bench.py")
        spec = importlib.util.spec_from_file_location("overload_bench", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        # alert_every=2: even a heavily contended box that only gets a
        # handful of paced sends through per phase still offers alerts
        result = mod.run(width=64, duration_s=0.2, multipliers=(1.0, 4.0),
                         alert_every=2)
        assert result["capacity_rows_per_s"] > 0
        assert len(result["rows"]) == 2
        for row in result["rows"]:
            assert row["goodput_rows_per_s"] > 0
            # the acceptance invariant: alert-class events never shed
            assert row["alert_sheds"] == 0
            assert row["alerts_offered"] > 0
        # the rendered table includes every multiplier
        table = mod._render(result)
        assert "(1.0x)" in table and "(4.0x)" in table


# ---------------------------------------------------------------------------
# the RPC fabric leg: a shedding owner answers a RETRYABLE code
# ---------------------------------------------------------------------------

class TestRpcBackpressure:
    def test_shed_maps_to_retryable_overloaded_code(self):
        """Cross-host forwarding: the owning host's admission refusal
        must reach the forwarding peer as ``overloaded`` — retryable,
        like an unreachable peer (the spool redelivers) — never as an
        opaque ``internal`` error that dead-letters rows the owner
        will accept once it recovers."""
        from sitewhere_tpu.rpc.channel import RpcChannel, RpcError
        from sitewhere_tpu.rpc.server import RpcServer

        srv = RpcServer(port=0)

        def shedding_ingest(ctx, body):
            raise OverloadShed(PriorityClass.TELEMETRY,
                               OverloadState.SHEDDING, 1.0)

        srv.register("events.ingest", shedding_ingest, auth_required=False)
        srv.start()
        try:
            chan = RpcChannel(srv.endpoint)
            with pytest.raises(RpcError) as exc:
                chan.call("events.ingest", {}, attachment=b"{}")
            assert exc.value.error == "overloaded"
            chan.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# a plan in flight across a checkpoint save (PERF.md §6, PR 28): the
# dispatcher's own lag signal into the ladder as Instance wires it,
# every clock injected
# ---------------------------------------------------------------------------

class TestPlanInFlightAcrossASave:
    """What PR 25's two shed sends were read as, pinned: a background save
    that holds the GIL ages the one plan it catches in flight, and the
    ladder must tell that from a wedged pipeline.  Times are the cell's
    (``fleet-1m.wire-steady``, my chip runs, PR 28): a plan lives ~23 ms,
    the save's longest hold is the stores' pickle, ~0.8 s of a 1.3–1.4 s
    save."""

    STEP_TO_SEAL_S = 0.023

    @pytest.fixture
    def wired(self, monkeypatch):
        from test_host_pipeline import ingest_window_at, make_ring_dispatcher

        clock = FakeClock()
        monkeypatch.setattr(time, "monotonic", clock)
        disp, store, _ = make_ring_dispatcher(
            ring_depth=0, egress_offload=False)   # not started: no threads
        disp.batcher.clock = clock                # stamps plan.created_at
        disp.steps = 1                            # past the warm-up gate
        ladder = OverloadController(
            confirm_samples=2, sample_interval_s=0.1,   # runtime/config.py
            signals_fn=lambda: OverloadSignals(
                seal_lag_s=disp.oldest_unsealed_wait_s()),
            clock=clock, metrics=MetricsRegistry())

        def dispatch():
            ingest_window_at(disp, 0)
            assert len(disp._inflight) == 1
        return clock, disp, store, ladder, dispatch

    def test_a_plan_that_seals_in_time_never_reads_as_lag(self, wired):
        clock, disp, store, ladder, dispatch = wired
        shed_at = Watermarks().seal_lag_s
        for _ in range(20):                # a payload every 1.6 s
            dispatch()
            clock.t += self.STEP_TO_SEAL_S
            assert 0.0 < disp.oldest_unsealed_wait_s() < shed_at[0]
            assert ladder.tick() == OverloadState.NORMAL
            disp.flush()
            assert disp.oldest_unsealed_wait_s() == 0.0
            clock.t += 1.6 - self.STEP_TO_SEAL_S
            assert ladder.tick() == OverloadState.NORMAL
        assert ladder.transitions == 0

    @pytest.mark.parametrize("hold_s", [0.8, 1.4])
    def test_one_plan_caught_by_the_save_does_not_shed(self, wired, hold_s):
        """The hold starves the dispatch loop and the egress worker alike;
        when it ends the loop's first sample reads the plan's whole age,
        past the SHEDDING watermark — and the plan seals before the
        second sample the ladder asks for, so no send is refused."""
        clock, disp, store, ladder, dispatch = wired
        degraded, shedding, _ = Watermarks().seal_lag_s
        dispatch()
        clock.t += 0.005 + hold_s          # caught 5 ms into its life
        assert disp.oldest_unsealed_wait_s() >= shedding
        assert ladder.tick() == OverloadState.NORMAL     # one sample
        disp.flush()                       # the worker gets the GIL back
        clock.t += 0.1
        assert disp.oldest_unsealed_wait_s() < degraded
        assert ladder.tick() == OverloadState.NORMAL
        assert ladder.admit(PriorityClass.TELEMETRY, n=1024)
        assert ladder.transitions == 0 and store.rows > 0

    def test_a_plan_that_stays_in_flight_still_sheds(self, wired):
        """The same age held over two samples is a wedged pipeline: the
        ladder goes to SHEDDING, refuses telemetry, and comes back one
        cooldown after the plan seals."""
        clock, disp, store, ladder, dispatch = wired
        dispatch()
        clock.t += 0.8
        ladder.tick()
        clock.t += 0.1
        assert ladder.tick() == OverloadState.SHEDDING
        assert ladder.last_driver == "seal_lag_s"
        assert not ladder.admit(PriorityClass.TELEMETRY, n=1024)
        disp.flush()
        clock.t += 0.1
        ladder.tick()
        clock.t += ladder.cooldown_s
        assert ladder.tick() == OverloadState.NORMAL
        assert ladder.admit(PriorityClass.TELEMETRY, n=1024)

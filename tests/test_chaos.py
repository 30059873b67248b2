"""Deterministic chaos: failure paths driven through runtime.faults.

Every test arms a named injection point (``runtime/faults.py``) and
proves the pipeline's contract under that failure:

- a killed receiver restarts under its supervisor with exponential
  backoff, and a QoS-1 publish whose intake crashed is NOT acked — the
  device redelivers and zero events are lost;
- an open circuit breaker SHEDS outbound batches (counted + summarized
  to dead letters) instead of queueing behind a dead sink;
- event-store seal failures retry a bounded number of times, then
  dead-letter the chunk without stalling the flush path;
- a step/egress fault leaves the journal offset uncommitted (the commit
  gate fails closed) so a restart replays the rows — at-least-once;
- a journaled pre-hardening record with an out-of-int32 ``eventDate``
  dead-letters during replay instead of aborting instance boot.

All faults are seeded/counted — each run is bit-identical.
"""

import json
import socket
import time

import numpy as np
import pytest

from sitewhere_tpu.runtime import faults
from sitewhere_tpu.runtime.resilience import (
    CircuitBreaker,
    CollectingSink,
    RetryPolicy,
)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.device_clear()
    yield
    faults.clear()
    faults.device_clear()


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


# ---------------------------------------------------------------------------
# the injection registry itself
# ---------------------------------------------------------------------------

class TestFaultRegistry:
    def test_unarmed_fire_is_noop(self):
        assert not faults.active()
        faults.fire("nowhere")  # must not raise, must not allocate state
        assert faults.hits("nowhere") == 0

    def test_after_n_skips_then_raises(self):
        faults.inject("p", after_n=2, times=1)
        faults.fire("p")
        faults.fire("p")
        with pytest.raises(faults.FaultInjected):
            faults.fire("p")
        faults.fire("p")  # times=1 spent
        assert faults.hits("p") == 4
        assert faults.fired("p") == 1

    def test_times_none_is_permanent(self):
        faults.inject("p", times=None)
        for _ in range(5):
            with pytest.raises(faults.FaultInjected):
                faults.fire("p")
        assert faults.fired("p") == 5

    def test_custom_exception_instance_and_class(self):
        faults.inject("p", exc=OSError("disk gone"), times=None)
        with pytest.raises(OSError, match="disk gone"):
            faults.fire("p")
        faults.inject("q", exc=ValueError, times=None)
        with pytest.raises(ValueError, match="injected fault at 'q'"):
            faults.fire("q")

    def test_probability_is_seed_deterministic(self):
        def run(seed):
            faults.inject("p", probability=0.5, times=None, seed=seed)
            out = []
            for _ in range(32):
                try:
                    faults.fire("p")
                    out.append(0)
                except faults.FaultInjected:
                    out.append(1)
            faults.clear("p")
            return out

        a, b = run(1234), run(1234)
        assert a == b               # same seed → identical schedule
        assert 0 < sum(a) < 32      # actually probabilistic
        assert run(99) != a         # different seed → different draw

    def test_injected_context_disarms_even_on_error(self):
        with pytest.raises(RuntimeError):
            with faults.injected("p"):
                raise RuntimeError("test body blew up")
        assert not faults.active()


# ---------------------------------------------------------------------------
# killed receiver → supervised restart with backoff
# ---------------------------------------------------------------------------

class TestReceiverRecovery:
    def test_udp_receiver_restarts_with_backoff(self):
        from sitewhere_tpu.ingest.sources import UdpReceiver

        rx = UdpReceiver(port=0)
        rx.restart_policy = RetryPolicy(initial_s=0.01, max_s=0.1)
        got = []
        rx.sink = got.append
        rx.start()
        try:
            addr = ("127.0.0.1", rx.port)
            tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            # the first datagram's emit crashes the receive loop
            faults.inject("ingest.emit", times=1)
            tx.sendto(b"poison", addr)
            assert _wait(lambda: rx.supervisor.restarts == 1)
            assert rx.supervisor.restart_delays == pytest.approx([0.01])
            # restarted loop (same bound socket) keeps receiving
            assert _wait(lambda: rx.supervisor.alive)
            assert not rx.supervisor.escalated

            def feed():
                # UDP is lossy by design: nudge until the restarted loop
                # picks one up (distinct from the supervised-crash path)
                tx.sendto(b"after-restart", addr)
                return got

            assert _wait(feed)
            assert got[-1] == b"after-restart"
            tx.close()
        finally:
            rx.stop()

    def test_tcp_emit_fault_is_connection_local(self):
        """An ``ingest.emit`` crash inside one connection's framing loop
        kills ONLY that connection (the un-acked stream is the client's
        cue to resend — TCP redelivery); the supervised accept loop
        never restarts for it."""
        from sitewhere_tpu.ingest.sources import TcpReceiver, newline_frames

        rx = TcpReceiver(port=0, framing=newline_frames)
        got = []
        rx.sink = got.append
        rx.start()
        try:
            faults.inject("ingest.emit", times=1)
            tx = socket.create_connection(("127.0.0.1", rx.port), timeout=5)
            tx.sendall(b"poison\n")
            # the poisoned connection is closed by the receiver
            tx.settimeout(5)
            assert tx.recv(1) == b""
            tx.close()
            assert _wait(lambda: rx.connection_errors == 1)
            assert rx.supervisor.restarts == 0
            assert not got
            # the client's redelivery path: reconnect and resend
            tx = socket.create_connection(("127.0.0.1", rx.port), timeout=5)
            tx.sendall(b"after-reconnect\n")
            assert _wait(lambda: got)
            assert got[-1] == b"after-reconnect"
            tx.close()
        finally:
            rx.stop()

    def test_tcp_sink_value_error_is_counted_not_swallowed(self):
        """A sink raising ValueError is a sink crash, not a framing
        violation: it must tick ``connection_errors`` (monitoring) and
        stay connection-local."""
        from sitewhere_tpu.ingest.sources import TcpReceiver, newline_frames

        rx = TcpReceiver(port=0, framing=newline_frames)

        def bad_sink(payload):
            raise ValueError("decode exploded")

        rx.sink = bad_sink
        rx.start()
        try:
            tx = socket.create_connection(("127.0.0.1", rx.port), timeout=5)
            tx.sendall(b"anything\n")
            assert _wait(lambda: rx.connection_errors == 1)
            assert rx.supervisor.restarts == 0
            tx.close()
        finally:
            rx.stop()

    def test_tcp_accept_loop_restarts_and_rebinds_same_port(self):
        """Accept-loop death (socket dies under it) restarts under the
        supervisor with backoff and re-binds the SAME port, so clients
        just reconnect."""
        from sitewhere_tpu.ingest.sources import TcpReceiver, newline_frames

        rx = TcpReceiver(port=0, framing=newline_frames)
        rx.restart_policy = RetryPolicy(initial_s=0.01, max_s=0.1)
        got = []
        rx.sink = got.append
        rx.start()
        try:
            port = rx.port
            # the accept loop's socket dies under it (shutdown wakes a
            # BLOCKED accept — close alone would not, on Linux)
            rx._sock.shutdown(socket.SHUT_RDWR)
            assert _wait(lambda: rx.supervisor.restarts >= 1)
            assert not rx.supervisor.escalated

            def feed():
                # reconnect until the restarted loop has re-bound
                try:
                    tx = socket.create_connection(("127.0.0.1", port),
                                                  timeout=1)
                except OSError:
                    return False
                tx.sendall(b"after-restart\n")
                tx.close()
                return _wait(lambda: got, timeout=1.0)

            assert _wait(feed)
            assert got[-1] == b"after-restart"
            assert rx.port == port
        finally:
            rx.stop()

    def test_stomp_emit_crash_leaves_message_unacked_for_redelivery(self):
        """STOMP slice of the remaining-receiver chaos coverage: the
        receiver loop now runs supervised, and an ``ingest.emit`` crash
        stays message-local — the MESSAGE is left UNACKED (the broker's
        redelivery cue, at-least-once) without restarting the session
        loop, and the redelivered copy lands and acks."""
        from sitewhere_tpu.ingest.stomp import StompReceiver

        from test_stomp_http import MiniBroker

        broker = MiniBroker()
        got = []
        rx = StompReceiver("127.0.0.1", broker.port,
                           destination="/queue/q", heartbeat_ms=0,
                           reconnect_delay_s=0.05)
        rx.sink = got.append
        rx.start()
        try:
            assert _wait(lambda: broker.subscribes)
            # supervised loop (ROADMAP open item, STOMP slice)
            assert rx.supervisor is not None and rx.supervisor.alive
            assert rx.acks_on_emit  # client-individual gates ACK on emit
            faults.inject("ingest.emit", times=1)
            broker.push("m-1", b"ev-1")
            assert _wait(lambda: rx.emit_errors == 1)
            assert broker.acks == []           # crashed intake: no ACK
            assert got == []
            assert rx.supervisor.restarts == 0  # crash was message-local
            # broker-side at-least-once: redelivery lands and acks
            broker.push("m-1", b"ev-1")
            assert _wait(lambda: got == [b"ev-1"])
            assert _wait(lambda: broker.acks == ["m-1"])
        finally:
            rx.stop()
            broker.close()

    def test_mqtt_qos1_intake_crash_loses_no_events(self):
        """The acceptance proof: a crashed intake withholds the PUBACK,
        the device redelivers, and the event lands exactly as published —
        zero QoS-1 loss across the receiver failure."""
        from sitewhere_tpu.ingest.mqtt import MqttClient
        from sitewhere_tpu.ingest.mqtt_broker import MqttBrokerReceiver

        rx = MqttBrokerReceiver(topic_filter="sitewhere/input/#")
        got = []
        rx.sink = got.append
        rx.start()
        try:
            dev = MqttClient("127.0.0.1", rx.port, client_id="dev-chaos")
            dev.connect()
            # intake crashes on the first emit: broker must NOT ack
            faults.inject("ingest.emit", times=1)
            dev.publish("sitewhere/input/dev-chaos", b"ev-1", qos=1)
            assert not dev.drain_publishes(timeout=5.0)  # no PUBACK came
            assert _wait(lambda: rx.broker.tap_failures == 1)
            assert got == []  # the crashed attempt delivered nothing
            dev.disconnect()

            # device-side at-least-once: reconnect and redeliver
            dev2 = MqttClient("127.0.0.1", rx.port, client_id="dev-chaos")
            dev2.connect()
            dev2.publish("sitewhere/input/dev-chaos", b"ev-1", qos=1)
            assert dev2.drain_publishes(timeout=10.0)  # PUBACKed now
            assert got == [b"ev-1"]                    # zero loss
            dev2.disconnect()
        finally:
            rx.stop()


# ---------------------------------------------------------------------------
# open breaker sheds outbound load
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _cols(n=4):
    # minimal outbound columns: no filters attached, so only the fields
    # marshal_row would touch matter — and CallbackConnector skips it
    return {"device_id": np.arange(n, dtype=np.int32)}


class TestBreakerSheds:
    def test_connector_sheds_when_open_and_recovers(self):
        from sitewhere_tpu.outbound.connectors import CallbackConnector

        clock = FakeClock()
        sink = CollectingSink()
        breaker = CircuitBreaker(name="chaos-conn", min_calls=2,
                                 failure_threshold=1.0, open_for_s=5.0,
                                 clock=clock)
        delivered = []
        conn = CallbackConnector(
            "chaos-conn", lambda c, m: delivered.append(int(m.sum())),
            breaker=breaker, dead_letters=sink)
        mask = np.ones(4, np.bool_)

        faults.inject("outbound.deliver", exc=OSError, times=2)
        for _ in range(2):
            with pytest.raises(OSError):
                conn.process_batch(_cols(), mask)
        assert breaker.state == CircuitBreaker.OPEN

        # open: batches are SHED (no queueing, no deliver call) and the
        # shed volume is summarized to the dead-letter sink
        assert conn.process_batch(_cols(), mask) == 0
        assert conn.process_batch(_cols(), mask) == 0
        assert delivered == []
        assert conn.shed == 8
        kinds = [r["kind"] for r in sink.records]
        assert kinds == ["connector-shed", "connector-shed"]
        assert sum(r["rows"] for r in sink.records) == 8

        # sink recovers: the half-open probe re-admits traffic
        clock.t = 5.0
        assert conn.process_batch(_cols(), mask) == 4
        assert breaker.state == CircuitBreaker.CLOSED
        assert delivered == [4]
        assert conn.processed == 4

    def test_http_rejections_trip_the_breaker(self):
        """A webhook that answers with errors is a FAILING sink: the
        connector raises DeliveryFailed (counted) and the breaker trips
        and sheds — it must never record a rejected POST as success."""
        import http.server
        import threading

        from sitewhere_tpu.outbound.connectors import (
            DeliveryFailed,
            HttpConnector,
        )

        from test_outbound import make_cols

        class Reject(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                self.send_response(503)
                self.end_headers()

            def log_message(self, *a):
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Reject)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            clock = FakeClock()
            breaker = CircuitBreaker(name="webhook", min_calls=2,
                                     failure_threshold=1.0, open_for_s=5.0,
                                     clock=clock)
            conn = HttpConnector(
                "webhook", f"http://127.0.0.1:{srv.server_address[1]}/in",
                breaker=breaker)
            mask = np.ones(4, np.bool_)
            for _ in range(2):
                with pytest.raises(DeliveryFailed):
                    conn.process_batch(make_cols(4), mask)
            assert conn.errors == 2
            assert breaker.state == CircuitBreaker.OPEN
            # open: batches shed without touching the webhook
            assert conn.process_batch(make_cols(4), mask) == 0
            assert conn.shed == 4
        finally:
            srv.shutdown()
            srv.server_close()


# ---------------------------------------------------------------------------
# event-store flush: retry then dead-letter, never stall
# ---------------------------------------------------------------------------

class TestEventStoreFlushChaos:
    def test_seal_retries_then_dead_letters_without_stalling(self, tmp_path):
        from sitewhere_tpu.services.event_store import EventStore

        from test_event_store import make_cols

        sink = CollectingSink()
        store = EventStore(str(tmp_path), flush_rows=1000,
                           flush_interval_s=1000, dead_letters=sink,
                           max_seal_retries=2, seal_retry_window_s=0.0)
        store.append_columns(make_cols(10))
        faults.inject("event_store.flush", exc=OSError("disk full"),
                      times=None)
        # bounded retries: each sync flush surfaces the failure...
        for _ in range(store.max_seal_retries):
            with pytest.raises(OSError):
                store.flush()
        assert store.total_events == 10  # columns still resident
        # ...then the chunk dead-letters and flush succeeds again — the
        # commit gate's sync flush is unblocked (no stall, bounded memory)
        store.flush()
        assert store.sealed_dead_lettered == 10
        assert store.total_events == 0
        [rec] = sink.records
        assert rec["kind"] == "event-flush-failed"
        assert rec["rows"] == 10
        assert "disk full" in rec["error"]

        # the store is still live: healthy appends flush durably
        faults.clear("event_store.flush")
        store.append_columns(make_cols(5))
        assert store.flush() == 5
        assert store.total_events == 5

    def test_seal_retry_budget_is_wall_clock_not_ticks(self, tmp_path):
        """The flusher ticks every flush_interval_s: an attempt count
        alone would burn the whole retry budget in seconds and drop data
        over a transient disk blip.  Until seal_retry_window_s of wall
        clock has passed, exhausted attempts keep retrying."""
        from sitewhere_tpu.services.event_store import EventStore

        from test_event_store import make_cols

        sink = CollectingSink()
        store = EventStore(str(tmp_path), flush_rows=1000,
                           flush_interval_s=1000, dead_letters=sink,
                           max_seal_retries=1, seal_retry_window_s=60.0)
        store.append_columns(make_cols(5))
        faults.inject("event_store.flush", exc=OSError("blip"), times=None)
        for _ in range(5):  # attempts well past max_seal_retries
            with pytest.raises(OSError):
                store.flush()
        assert store.sealed_dead_lettered == 0
        assert store.total_events == 5
        # the "blip" ends: everything seals, nothing was dropped
        faults.clear("event_store.flush")
        store.flush()
        assert store.total_events == 5
        assert len(sink.records) == 0

    def test_broken_dead_letter_sink_never_drops_rows(self, tmp_path):
        """When the dead-letter write itself fails (often the same dead
        disk), the chunk must stay resident and the sync flush must keep
        failing — dropping it would be silent data loss."""
        from sitewhere_tpu.services.event_store import EventStore

        from test_event_store import make_cols

        class BrokenSink:
            def append_json(self, doc):
                raise OSError("dead-letter disk gone too")

        store = EventStore(str(tmp_path), flush_rows=1000,
                           flush_interval_s=1000, dead_letters=BrokenSink(),
                           max_seal_retries=1, seal_retry_window_s=0.0)
        store.append_columns(make_cols(10))
        faults.inject("event_store.flush", exc=OSError("disk full"),
                      times=None)
        # well past max_seal_retries: every sync flush still refuses
        for _ in range(4):
            with pytest.raises(OSError):
                store.flush()
        assert store.total_events == 10
        assert store.sealed_dead_lettered == 0
        # the dead-letter sink recovers first: next flush dead-letters
        # the chunk and unwedges the store
        store.dead_letters = CollectingSink()
        store.flush()
        assert store.sealed_dead_lettered == 10
        assert len(store.dead_letters.records) == 1


# ---------------------------------------------------------------------------
# dispatcher: fail closed, replay on restart (at-least-once)
# ---------------------------------------------------------------------------

def _instance_config(tmp_path, **pipeline):
    from sitewhere_tpu.runtime.config import Config

    return Config({
        "instance": {"id": "chaos-inst", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 128,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1,
                     **pipeline},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
    }, apply_env=False)


def _seed_device(inst, token="d-0"):
    inst.device_management.create_device_type(token="sensor", name="Sensor")
    inst.device_management.create_device(token=token, device_type="sensor")
    inst.device_management.create_device_assignment(device=token)


def _measurement_line(token, value, event_date):
    return json.dumps({
        "deviceToken": token, "type": "Measurement",
        "request": {"name": "temp", "value": value,
                    "eventDate": event_date},
    })


class TestDispatcherChaos:
    def test_egress_worker_killed_mid_window_then_replays(self, tmp_path):
        """Acceptance (overlapped host pipeline): the egress fault kills
        the OFFLOAD WORKER mid-window; its supervisor restarts the loop,
        but the dead plan never completes — the journal offset is never
        committed past it, and replay after 'restart' recovers the rows
        exactly once (at-least-once under offloaded egress)."""
        from sitewhere_tpu.instance import Instance

        # offload is backend-adaptive (off on CPU) — force it on so the
        # fault lands on the supervised worker, not the inline fallback
        inst = Instance(_instance_config(tmp_path, egress_offload=True))
        inst.start()
        try:
            _seed_device(inst)
            payload = _measurement_line("d-0", 7.0, 1_753_800_000).encode()
            faults.inject("dispatcher.egress", times=1)
            inst.dispatcher.ingest_wire_lines(payload)
            # the offloaded egress took the plan and died on it
            assert _wait(lambda: faults.fired("dispatcher.egress") == 1)
            assert _wait(lambda: inst.dispatcher._egress_super.restarts >= 1)
            assert not inst.dispatcher._egress_super.escalated
            # journaled, but the dead plan keeps the commit gate closed
            assert inst.ingest_journal.end_offset == 1
            inst.dispatcher.flush(timeout_s=0.05)
            assert inst.dispatcher.journal_reader.committed == 0
            inst.event_store.flush()
            assert inst.event_store.total_events == 0

            # the restarted worker still serves its siblings (the dead
            # plan keeps the outstanding gate >0, so bound the flush)
            payload2 = _measurement_line("d-0", 8.0, 1_753_800_001).encode()
            inst.dispatcher.ingest_wire_lines(payload2)
            assert _wait(lambda: inst.dispatcher.totals["accepted"] == 1)
            inst.dispatcher.flush(timeout_s=0.5)
            inst.event_store.flush()
            assert inst.event_store.total_events == 1
            # ...but the offset STILL must not move past the dead plan
            assert inst.dispatcher.journal_reader.committed == 0

            # "restart": the crash loses the in-memory outstanding count;
            # replay re-ingests from the committed offset.  Both records
            # replay (at-least-once re-delivers the sibling too: same
            # semantics as a Kafka consumer rewound to its offset).
            with inst.dispatcher._lock:
                inst.dispatcher._plans_outstanding = 0
            replayed = inst.dispatcher.replay_journal()
            assert replayed == 2
            inst.event_store.flush()
            assert inst.event_store.total_events == 3
            assert inst.dispatcher.journal_reader.committed == 2
        finally:
            inst.stop()
            inst.terminate()

    def test_egress_crash_mid_ring_replays_exactly_the_uncommitted(
            self, tmp_path):
        """Device-resident ring under chaos: two full windows dispatch as
        ONE chained program; the egress fault kills slot 0's plan, slot 1
        still lands, the journal offset never moves past the dead step,
        and a 'restart' replay re-ingests from the committed offset —
        the uncommitted step's rows recover (at-least-once; the sibling
        re-delivers too, Kafka-rewind semantics)."""
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(
            tmp_path, egress_offload=True, ring_depth=2,
            deadline_ms=60_000.0))
        inst.start()
        try:
            inst.device_management.create_device_type(
                token="sensor", name="Sensor")
            for i in range(64):
                inst.device_management.create_device(
                    token=f"d-{i}", device_type="sensor")
                inst.device_management.create_device_assignment(
                    device=f"d-{i}")
            width = 64

            def payload(r):
                return "\n".join(
                    _measurement_line(f"d-{i}", 7.0, 1_753_800_000 + r)
                    for i in range(width)).encode()

            faults.inject("dispatcher.egress", times=1)
            inst.dispatcher.ingest_wire_lines(payload(0))
            inst.dispatcher.ingest_wire_lines(payload(1))  # chain of 2
            assert _wait(lambda: faults.fired("dispatcher.egress") == 1)
            assert inst.dispatcher.metrics_snapshot()["ring_chains"] == 1
            inst.dispatcher.flush(timeout_s=0.5)
            # slot 1 (the sibling step) landed; slot 0 stays outstanding
            inst.event_store.flush()
            assert inst.event_store.total_events == width

            # flight recorder (ISSUE 9 satellite): the chaos-injected
            # egress crash must have dumped a snapshot containing the
            # crashed chain's records — the failed slot with its error
            # attributed, the surviving sibling committed
            from sitewhere_tpu.runtime.flightrec import parse_snapshot

            snaps = inst.flightrec.snapshots()
            crash = [s for s in snaps if "egress-crash" in s["name"]]
            assert crash, f"no egress-crash snapshot in {snaps}"
            snap = parse_snapshot(
                inst.flightrec.read_snapshot(crash[0]["name"]))
            failed = [r for r in snap["records"]
                      if r["commit"] == "failed"]
            assert len(failed) == 1 and failed[0]["slot"] == 0
            assert "error" in failed[0]
            with inst.dispatcher._lock:
                assert inst.dispatcher._plans_outstanding == 1
            assert inst.ingest_journal.end_offset == 2
            assert inst.dispatcher.journal_reader.committed == 0

            # "restart": the crash loses the outstanding count; replay
            # re-ingests BOTH journal records past the committed offset
            # (the replayed full windows ride the ring again)
            with inst.dispatcher._lock:
                inst.dispatcher._plans_outstanding = 0
            replayed = inst.dispatcher.replay_journal()
            assert replayed == 2 * width
            inst.event_store.flush()
            assert inst.event_store.total_events == 3 * width
            assert inst.dispatcher.journal_reader.committed == 2
        finally:
            faults.clear()
            inst.stop()
            inst.terminate()

    def test_step_fault_fails_closed_then_replays(self, tmp_path):
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_device(inst)
            payload = _measurement_line("d-0", 7.0, 1_753_800_000).encode()
            faults.inject("dispatcher.step", times=1)
            try:
                inst.dispatcher.ingest_wire_lines(payload)
            except faults.FaultInjected:
                pass  # the ingest thread itself took the plan
            # either the ingest path or the deadline-tick loop thread
            # takes the plan; whichever runs it dies at the step fault
            assert _wait(lambda: faults.fired("dispatcher.step") == 1)
            # journaled, but the dead plan keeps the commit gate closed:
            # the offset must never move past an unprocessed record
            assert inst.ingest_journal.end_offset == 1
            inst.dispatcher.flush(timeout_s=0.05)
            assert inst.dispatcher.journal_reader.committed == 0
            assert inst.event_store.total_events == 0

            # "restart": a crash loses the in-memory outstanding-plan
            # count with the process; replay re-ingests from the
            # committed offset and the row lands exactly once
            with inst.dispatcher._lock:
                inst.dispatcher._plans_outstanding = 0
            replayed = inst.dispatcher.replay_journal()
            assert replayed == 1
            inst.event_store.flush()
            assert inst.event_store.total_events == 1
            assert inst.dispatcher.journal_reader.committed == 1
        finally:
            inst.stop()
            inst.terminate()

    def test_nonfatal_step_fault_replays_without_restart(self, tmp_path):
        """ISSUE 16 satellite: the ``dispatcher.step`` seam with a
        NON-fatal exception class (an arbitrary runtime error, not a
        SIGKILL crosspoint and not the registry's own marker type).
        The gate must fail closed exactly as for a crash, but recovery
        runs IN PROCESS: ``replay_journal`` on the same live instance
        re-drives the rows, the same state manager keeps committing
        (no rebuild), and the offset commits past the record."""
        from sitewhere_tpu.instance import Instance

        class ChipBurp(RuntimeError):
            pass

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        try:
            _seed_device(inst)
            sm = inst.device_state
            payload = _measurement_line("d-0", 9.5, 1_753_800_000).encode()
            faults.inject("dispatcher.step", exc=ChipBurp("transient"),
                          times=1)
            try:
                inst.dispatcher.ingest_wire_lines(payload)
            except ChipBurp:
                pass  # the ingest thread took the plan itself
            assert _wait(lambda: faults.fired("dispatcher.step") == 1)
            assert inst.ingest_journal.end_offset == 1
            inst.dispatcher.flush(timeout_s=0.05)
            # fail-closed: journaled but neither stored nor committed
            assert inst.dispatcher.journal_reader.committed == 0
            assert inst.event_store.total_events == 0

            # in-process recovery: reap the dead plan's accounting (its
            # rows are exactly what the replay below re-drives), then
            # replay on the SAME instance — no restart, no state rebuild
            with inst.dispatcher._lock:
                inst.dispatcher._plans_outstanding = 0
            assert inst.dispatcher.replay_journal() == 1
            inst.event_store.flush()
            assert inst.event_store.total_events == 1
            assert inst.dispatcher.journal_reader.committed == 1
            # the packed epoch re-leased on the surviving manager: same
            # object, and the replayed row's state committed through it
            assert inst.device_state is sm
            assert 9.5 in sm.get_device_state("d-0")["last_values"]
        finally:
            inst.stop()
            inst.terminate()


# ---------------------------------------------------------------------------
# journal replay of a corrupt pre-hardening record (ADVICE high finding)
# ---------------------------------------------------------------------------

class TestCorruptJournalReplay:
    def test_out_of_int32_event_date_dead_letters_and_boot_completes(
            self, tmp_path):
        """Regression: `_replay_columnar` used to let the native lane's
        DecodeError (finite out-of-int32 eventDate — a record a
        pre-hardening build journaled happily) abort replay, and with it
        instance boot.  It must fall through to the scalar decoder's
        dead-letter path instead."""
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(tmp_path))
        inst.start()
        _seed_device(inst)
        # 1e10 epoch-seconds: finite, below the millis heuristic, out of
        # int32 — exactly what pre-hardening code journaled unchecked.
        bad = _measurement_line("d-0", 1.0, 10_000_000_000).encode()
        good = _measurement_line("d-0", 2.0, 1_753_800_000).encode()
        inst.ingest_journal.append(bad)
        inst.ingest_journal.append(good)
        inst.stop()
        inst.terminate()

        inst2 = Instance(_instance_config(tmp_path))
        inst2.start()  # this is the assertion: boot must not raise
        try:
            # the bad record dead-lettered; its sibling replayed fine
            snap = inst2.dispatcher.metrics_snapshot()
            assert snap["accepted"] == 1
            kinds = [
                json.loads(inst2.dead_letters.read_one(i)).get("kind")
                for i in range(inst2.dead_letters.end_offset)
            ]
            assert "failed-decode" in kinds
        finally:
            inst2.stop()
            inst2.terminate()


# ---------------------------------------------------------------------------
# command delivery retry under injected transport failure
# ---------------------------------------------------------------------------

class TestCommandDeliveryChaos:
    def _destination(self, sink, retry):
        from sitewhere_tpu.commands.destinations import (
            CallbackDeliveryProvider,
            CommandDestination,
        )

        return CommandDestination(
            "chaos-dest", encoder=lambda ex: b"payload",
            extractor=lambda ex: {}, retry=retry,
            provider=CallbackDeliveryProvider(
                lambda ex, payload, params: sink.append(payload)))

    def _execution(self):
        from sitewhere_tpu.commands.model import (
            CommandExecution,
            CommandInvocation,
        )

        inv = CommandInvocation(command_token="c", target_assignment="a")
        return CommandExecution(invocation=inv, command_name="c",
                                namespace="test")

    def test_transient_failures_retried_to_success(self):
        from sitewhere_tpu.commands.destinations import DeliveryError

        sink = []
        dest = self._destination(
            sink, RetryPolicy(initial_s=0.0, max_attempts=3))
        faults.inject("commands.deliver", exc=DeliveryError, times=2)
        dest.deliver(self._execution())
        assert sink == [b"payload"]
        assert faults.hits("commands.deliver") == 3

    def test_exhausted_retries_surface_as_delivery_error(self):
        from sitewhere_tpu.commands.destinations import DeliveryError

        sink = []
        dest = self._destination(
            sink, RetryPolicy(initial_s=0.0, max_attempts=2))
        faults.inject("commands.deliver", exc=DeliveryError, times=None)
        with pytest.raises(DeliveryError):
            dest.deliver(self._execution())
        assert sink == []


# ---------------------------------------------------------------------------
# remaining-receiver chaos coverage: AMQP / CoAP / EventHub (ROADMAP slice)
# ---------------------------------------------------------------------------

class TestRemainingReceiverChaos:
    """Per-protocol ``ingest.emit`` crash tests — the redelivery
    semantics differ per broker: AMQP 0-9-1 nacks with requeue, CoAP
    relies on the client's CON retransmission, Event Hub leaves the
    delivery unsettled and recycles the link.  All three loops now run
    under the shared receiver Supervisor."""

    def test_amqp_emit_crash_nacks_with_requeue(self):
        from sitewhere_tpu.ingest.amqp import AmqpReceiver

        from test_amqp import MiniAmqpBroker

        broker = MiniAmqpBroker()
        got = []
        rx = AmqpReceiver("127.0.0.1", broker.port, queue="q1")
        rx.sink = got.append
        rx.start()
        try:
            assert _wait(lambda: broker.sessions == 1)
            # supervised loop (ROADMAP open item, AMQP slice)
            assert rx.supervisor is not None and rx.supervisor.alive
            faults.inject("ingest.emit", times=1)
            broker.push(b"ev-1")
            assert _wait(lambda: rx.emit_errors == 1)
            # broker-native redelivery semantics: nack + requeue bit,
            # never an ack for the crashed attempt
            assert _wait(lambda: broker.nacks == [(1, 0x02)])
            # broker-side at-least-once: the requeued delivery comes
            # back and lands — zero loss across the intake crash
            assert _wait(lambda: got == [b"ev-1"])
            assert _wait(lambda: broker.acks == [2])
            assert rx.supervisor.restarts == 0  # crash was delivery-local
        finally:
            rx.stop()
            broker.close()

    def test_coap_emit_crash_retransmission_redelivers(self):
        from sitewhere_tpu.ingest.coap import (
            ACK,
            CHANGED_204,
            CoapServerReceiver,
            encode_post,
            parse_message,
        )

        rx = CoapServerReceiver(port=0)
        got = []
        rx.sink = got.append
        rx.start()
        try:
            # supervised loop (ROADMAP open item, CoAP slice)
            assert rx.supervisor is not None and rx.supervisor.alive
            client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            client.settimeout(0.3)
            request = encode_post("/events", b"ev-1", message_id=7)
            faults.inject("ingest.emit", times=1)
            client.sendto(request, ("127.0.0.1", rx.port))
            # crashed intake: NO ACK goes out — the client's CON
            # retransmission timer is the redelivery cue
            with pytest.raises(socket.timeout):
                client.recvfrom(65536)
            assert rx.emit_errors == 1
            assert got == []
            assert rx.supervisor.restarts == 0  # datagram-local crash
            # retransmit the SAME message id: the crashed attempt was
            # not cached as a duplicate, so it re-emits and acks
            client.settimeout(5.0)
            client.sendto(request, ("127.0.0.1", rx.port))
            data, _ = client.recvfrom(65536)
            reply = parse_message(data)
            assert (reply.mtype, reply.code) == (ACK, CHANGED_204)
            assert got == [b"ev-1"]
            assert rx.duplicates == 0
            client.close()
        finally:
            rx.stop()

    def test_eventhub_emit_crash_leaves_unsettled_and_redelivers(
            self, tmp_path):
        from sitewhere_tpu.ingest.amqp10 import EventHubReceiver

        from test_amqp10 import MiniEventHub

        broker = MiniEventHub(messages=[b"ev-1", b"ev-2"])
        got = []
        rx = EventHubReceiver(
            "127.0.0.1", broker.port, event_hub="hub", sasl="anonymous",
            credit=8, reconnect_delay_s=0.05,
            checkpoint_dir=str(tmp_path))
        rx.sink = got.append
        faults.inject("ingest.emit", times=1)
        rx.start()
        try:
            # supervised partition loop (ROADMAP open item, EventHub
            # slice); the crash is handled in-loop: the delivery stays
            # UNSETTLED + un-checkpointed and the link recycles, so the
            # broker redelivers — at-least-once, zero supervisor burn
            assert _wait(lambda: sorted(got) == [b"ev-1", b"ev-2"],
                         timeout=10.0)
            assert rx.emit_errors == 1
            assert broker.sessions >= 2   # recycle = the redelivery cue
            assert rx.supervisors and all(s.restarts == 0
                                          for s in rx.supervisors)
        finally:
            rx.stop()
            broker.close()


# ---------------------------------------------------------------------------
# overload: sustained 4x offered load degrades gracefully (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------

class TestOverloadChaos:
    def test_4x_sustained_load_sheds_telemetry_never_alerts(self, tmp_path):
        """Acceptance: offered load is 4× what the (pinned) emission
        window drains, sustained across the run.  Telemetry sheds are
        counted + dead-lettered + signalled (OverloadShed — the
        transports' 429/5.03/unacked translations are proven in
        tests/test_overload.py); alert-class events are NEVER shed and
        reach seal; the controller returns to NORMAL within one
        cooldown of the load dropping."""
        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.runtime.config import Config
        from sitewhere_tpu.runtime.overload import (
            OverloadShed,
            OverloadState,
        )

        width = 64
        cooldown_s = 0.3
        cfg = Config({
            "instance": {"id": "ov-chaos",
                         "data_dir": str(tmp_path / "data")},
            # the drain side is pinned: a 100s emission window means
            # nothing leaves the batcher during the storm — offered
            # rows accumulate as backlog, the watermark signal
            "pipeline": {"width": width, "registry_capacity": 128,
                         "mtype_slots": 4, "deadline_ms": 100_000.0,
                         "n_shards": 1, "adaptive_deadline": False},
            "presence": {"scan_interval_s": 3600.0,
                         "missing_after_s": 1800},
            "overload": {
                "enabled": True,
                "cooldown_s": cooldown_s,
                "sample_interval_s": 0.0,
                "watermarks": {
                    # DEGRADED at 25% of width, SHEDDING at 75%
                    "batcher_backlog": [0.25, 0.75, 8.0],
                    # backlog is THE driver under test: park the live
                    # seal-lag watermark out of reach so rows aging in
                    # the pinned window can't escalate on their own
                    "seal_lag_s": [600.0, 1200.0, 2400.0],
                },
            },
        }, apply_env=False)
        inst = Instance(cfg)
        inst.start()
        try:
            inst.device_management.create_device_type(token="sensor",
                                                      name="Sensor")
            inst.device_management.create_device(token="d-0",
                                                 device_type="sensor")
            inst.device_management.create_device_assignment(device="d-0")

            def telemetry_payload(i):
                return "\n".join(
                    json.dumps({"deviceToken": "d-0",
                                "type": "Measurement",
                                "request": {"name": "temp",
                                            "value": float(j),
                                            "eventDate": 1_753_800_000}})
                    for j in range(i * 8, i * 8 + 8)).encode()

            alert_payload = json.dumps({
                "deviceToken": "d-0", "type": "Alert",
                "request": {"type": "overheat", "level": "warning",
                            "message": "hot",
                            "eventDate": 1_753_800_000}}).encode()

            offered = 4 * width          # 4x the frozen drain window
            admitted_telemetry = 0
            signalled = 0
            alerts_sent = 0
            states_seen = set()
            for i in range(offered // 8):
                try:
                    admitted_telemetry += inst.dispatcher.ingest_wire_lines(
                        telemetry_payload(i), "chaos-src")
                except OverloadShed:
                    signalled += 1   # the transport-visible signal
                if i % 4 == 3:       # alerts ride along, sustained
                    inst.dispatcher.ingest_wire_lines(alert_payload,
                                                      "chaos-src")
                    alerts_sent += 1
                states_seen.add(inst.overload.tick())
            # the storm tripped the ladder and sheds were signalled
            assert OverloadState.SHEDDING in states_seen
            assert signalled > 0
            shed_rows = inst.metrics.counter(
                "overload.shed.telemetry").value
            assert shed_rows > 0
            assert admitted_telemetry + shed_rows == offered
            # zero alert sheds: every alert was admitted
            assert inst.metrics.counter("overload.shed.critical").value == 0
            # sheds are dead-lettered with class + reason (auditable)
            letters = [d for d in inst.list_dead_letters(limit=200)
                       if d.get("kind") == "intake-shed"]
            assert len(letters) == signalled
            assert all(d["classes"] == {"telemetry": 8} for d in letters)
            assert all(d["state"] in ("SHEDDING", "EMERGENCY")
                       for d in letters)

            # load drops: drain the backlog, then the controller must
            # return to NORMAL within ~one cooldown
            inst.dispatcher.flush()
            inst.event_store.flush()
            # every ADMITTED row — alerts included — reached seal
            assert inst.event_store.total_events \
                == admitted_telemetry + alerts_sent
            assert inst.dispatcher.totals["accepted"] \
                == admitted_telemetry + alerts_sent
            t0 = time.monotonic()
            while inst.overload.state != OverloadState.NORMAL \
                    and time.monotonic() - t0 < 5 * cooldown_s:
                inst.overload.tick()
                time.sleep(0.01)
            assert inst.overload.state == OverloadState.NORMAL
            assert time.monotonic() - t0 <= 2 * cooldown_s
        finally:
            inst.stop()
            inst.terminate()


# ---------------------------------------------------------------------------
# crash-consistent recovery (ISSUE 12): crosspoints + kill-mid-ring restart
# ---------------------------------------------------------------------------

class TestCrosspoints:
    """runtime.faults crosspoints: named SIGKILL points.  Unit tests run
    dry (hit accounting only) — actually dying is the harness's job."""

    def teardown_method(self):
        faults.disarm_crosspoint()

    def test_disarmed_is_noop(self):
        faults.disarm_crosspoint()
        faults.crosspoint("crash.mid_ring")  # must not raise or count

    def test_dry_run_counts_hits_after_n(self):
        faults.arm_crosspoint("crash.mid_seal", after_n=3, dry_run=True)
        for _ in range(5):
            faults.crosspoint("crash.mid_seal")
        assert faults.crosspoint_hits() == 5  # counted, never died
        faults.crosspoint("crash.other")      # different point: ignored
        assert faults.crosspoint_hits() == 5

    def test_env_spec_parsing(self, monkeypatch):
        monkeypatch.setenv("SW_CRASHPOINT", "crash.mid_egress:4")
        faults._parse_crosspoint_env()
        # armed for the 4th hit — but dry-run was not requested, so we
        # only verify the arming state, never cross it
        assert faults._kill_point == "crash.mid_egress"
        assert faults._kill_after == 4
        faults.disarm_crosspoint()


class TestKillMidRingRecovery:
    def test_kill_mid_ring_replay_is_bit_identical(self, tmp_path):
        """ISSUE 12 satellite: kill after the K-step chain dispatched
        but before ANY slot egressed (journal offset never moved), then
        a TRUE restart — fresh Instance on the survivor's data dir.  The
        replayed uncommitted slots must produce bit-identical device
        state to an un-killed control run, and the store must hold every
        journaled row exactly once."""
        from dataclasses import fields as dataclass_fields

        from sitewhere_tpu.instance import Instance

        width = 64

        def payload(r):
            return "\n".join(
                _measurement_line(f"d-{i}", float((r * width + i) % 37),
                                  1_753_860_000 + r * width + i)
                for i in range(width)).encode()

        def seed(inst):
            inst.device_management.create_device_type(
                token="sensor", name="Sensor")
            for i in range(width):
                inst.device_management.create_device(
                    token=f"d-{i}", device_type="sensor")
                inst.device_management.create_device_assignment(
                    device=f"d-{i}")

        # control: same traffic, never killed
        ctrl = Instance(_instance_config(
            tmp_path / "ctrl", egress_offload=True, ring_depth=2,
            deadline_ms=60_000.0))
        ctrl.start()
        try:
            seed(ctrl)
            ctrl.dispatcher.ingest_wire_lines(payload(0))
            ctrl.dispatcher.ingest_wire_lines(payload(1))
            ctrl.dispatcher.flush()
            ctrl.event_store.flush()
            golden_state = {
                f.name: np.asarray(getattr(ctrl.device_state.current,
                                           f.name))
                for f in dataclass_fields(ctrl.device_state.current)}
            golden_tokens = {
                f"d-{i}": ctrl.identity.device.lookup(f"d-{i}")
                for i in range(width)}
        finally:
            ctrl.stop()
            ctrl.terminate()

        # victim: model checkpointed (the anchor), then a 2-deep ring
        # chain dispatches and BOTH slots fail egress — the journal
        # offset never moves, exactly the mid-ring kill window.  The
        # dry-run crosspoint proves the harness's kill point is crossed
        # on this path.
        a = Instance(_instance_config(
            tmp_path / "victim", egress_offload=True, ring_depth=2,
            deadline_ms=60_000.0))
        a.start()
        seed(a)
        a.dispatcher.flush()
        a.checkpointer.save()
        faults.arm_crosspoint("crash.mid_ring", dry_run=True)
        faults.inject("dispatcher.egress", times=2)
        a.dispatcher.ingest_wire_lines(payload(0))
        a.dispatcher.ingest_wire_lines(payload(1))
        assert _wait(lambda: faults.fired("dispatcher.egress") == 2)
        assert faults.crosspoint_hits() >= 1, \
            "crash.mid_ring crosspoint not on the chain-dispatch path"
        faults.disarm_crosspoint()
        faults.clear()
        a.event_store.flush()
        assert a.event_store.total_events == 0       # nothing egressed
        assert a.dispatcher.journal_reader.committed == 0
        assert a.ingest_journal.end_offset == 2      # both journaled
        a.ingest_journal.close()
        a.dead_letters.close()
        del a  # simulated SIGKILL — no stop, no final checkpoint

        b = Instance(_instance_config(
            tmp_path / "victim", egress_offload=True, ring_depth=2,
            deadline_ms=60_000.0))
        assert b.restored
        b.start()  # replays both uncommitted journal records
        try:
            b.dispatcher.flush()
            b.event_store.flush()
            # zero committed-event loss, exactly once
            assert b.event_store.total_events == 2 * width
            assert b.dispatcher.journal_reader.committed == 2
            assert b.metrics.snapshot()["gauges"][
                "recovery.replay_events"] == 2 * width
            # identity survived the anchor checkpoint: same handles
            for i in range(width):
                assert b.identity.device.lookup(f"d-{i}") \
                    == golden_tokens[f"d-{i}"]
            # bit-identical device state vs the un-killed control
            for f in dataclass_fields(b.device_state.current):
                np.testing.assert_array_equal(
                    np.asarray(getattr(b.device_state.current, f.name)),
                    golden_state[f.name],
                    err_msg=f"device_state.{f.name} diverged after "
                            f"kill-mid-ring recovery")
        finally:
            b.stop()
            b.terminate()


class TestCrashRecBench:
    """tools/crashrec_bench.py: the kill-point harness itself."""

    def _run(self, *args, timeout=560):
        import os
        import subprocess
        import sys

        # one process per chip: a child that needs JAX runs on the CPU
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SW_CRASHPOINT", None)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        return subprocess.run(
            [sys.executable, os.path.join(root, "tools",
                                          "crashrec_bench.py"), *args],
            capture_output=True, text=True, timeout=timeout, env=env)

    def test_smoke_three_fixed_kill_points(self, tmp_path):
        """Tier-1: SIGKILL at mid-ring, mid-egress, mid-background-seal,
        mid-compaction-swap, pre-manifest and mid-forward-send on a
        small journal; every kill must recover with zero
        committed-event loss, a consistent segment catalog,
        golden-equal analytics, and exported recovery gauges (the
        mid-forward case instead proves the 2-host spool-tail replay)."""
        res = self._run("--smoke", "--json",
                        str(tmp_path / "crashrec.json"))
        assert res.returncode == 0, res.stdout + res.stderr
        doc = json.loads((tmp_path / "crashrec.json").read_text())
        assert doc["ok"] and doc["summary"]["killed"] == 6
        points = {k["point"] for k in doc["kills"]}
        assert {"crash.mid_seal", "crash.mid_compact",
                "crash.mid_forward"} <= points
        for kill in doc["kills"]:
            assert kill["killed"] and not kill["failures"]
            if kill["point"] == "crash.mid_forward":
                # fleet-shaped case: the spool tail replayed to the
                # owner's journal and drained to zero
                assert kill["spool_pending_after"] == 0
                assert kill["owner_journal_rows"] >= kill["spooled_rows"]
            else:
                assert kill["restore_s"] is not None

    @pytest.mark.slow
    def test_randomized_sweep(self, tmp_path):
        """Slow gate: a small randomized sweep across the full kill-point
        catalog (the ≥50-point acceptance sweep is the tool's own
        ``--sweep 50``)."""
        res = self._run("--sweep", "6", "--seed", "1234", "--json",
                        str(tmp_path / "crashrec.json"))
        assert res.returncode == 0, res.stdout + res.stderr
        doc = json.loads((tmp_path / "crashrec.json").read_text())
        assert doc["ok"] and doc["summary"]["killed"] == 6


class TestFleetChaosBench:
    """tools/fleet_chaos_bench.py: the 3-host fleet health-plane proof
    (ISSUE 14 acceptance — shed, partition, recover; smooth goodput)."""

    def test_smoke_shed_partition_recover(self, tmp_path):
        import os
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SW_CRASHPOINT", None)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        res = subprocess.run(
            [sys.executable,
             os.path.join(root, "tools", "fleet_chaos_bench.py"),
             "--smoke", "--json", str(tmp_path / "fleet.json")],
            capture_output=True, text=True, timeout=240, env=env)
        assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
        doc = json.loads((tmp_path / "fleet.json").read_text())
        assert doc["ok"]
        # the scripted failure walked the detector where it should
        assert doc["state_after_partition"] in ("SUSPECT", "DOWN")
        assert doc["edge_refusal"]["refused"]
        # bounded probes while unhealthy, zero forward dead letters,
        # spool drained, at-least-once toward the sick host
        for phase in ("shed", "partition"):
            p = doc["phases"][phase]
            assert p["sick_ingest_attempts"] <= p["attempt_budget"]
        assert doc["forward_dead_lettered"] == 0
        assert doc["pending_after_recovery"] == 0
        assert doc["sick_accepted_rows"] >= doc["sick_sent_rows"]


class TestDevFaultBench:
    """tools/devfault_bench.py --smoke: the ISSUE-16 acceptance proof
    (chain re-lease, breaker ladder, poison bisect + bit-identical
    state, quarantine via requeue, watchdog budgets)."""

    def test_smoke_contract_holds(self):
        import os
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SW_CRASHPOINT", None)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        res = subprocess.run(
            [sys.executable,
             os.path.join(root, "tools", "devfault_bench.py"),
             "--smoke", "--json"],
            capture_output=True, text=True, timeout=300, env=env)
        assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
        doc = json.loads(res.stdout)
        assert doc["ok"]
        ph = doc["phases"]
        assert ph["chain_fault"]["chain_faults"] == 1
        assert ph["chain_fault"]["releases"] == 1
        assert ph["breaker"]["trips"] == 2
        assert ph["breaker"]["restores"] == 1
        assert ph["poison"]["state_bit_identical"]
        assert ph["poison"]["quarantined_devices"] == 1
        assert ph["watchdog"]["hard_trips"] >= 1


class TestTenantFairnessBench:
    """tools/tenant_fairness_bench.py --smoke: the ISSUE-20 acceptance
    proof (quiet goodput floor under a noisy neighbor, configured budget
    clip with replayable tenant-budget dead letters, zero-loss
    accounting, churn-storm partition isolation)."""

    def test_smoke_contract_holds(self):
        import os
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SW_CRASHPOINT", None)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        res = subprocess.run(
            [sys.executable,
             os.path.join(root, "tools", "tenant_fairness_bench.py"),
             "--smoke", "--json"],
            capture_output=True, text=True, timeout=300, env=env)
        assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
        doc = json.loads(res.stdout)
        assert doc["ok"]
        by_name = {c["name"]: c for c in doc["checks"]}
        for name in ("quiet_goodput_floor", "quiet_never_shed",
                     "noisy_clipped_to_budget",
                     "budget_sheds_dead_lettered",
                     "shedding_refuses_telemetry_not_critical",
                     "recovery_restores_noisy_and_replays_budget_sheds",
                     "zero_rows_lost", "accepted_rows_sealed",
                     "churn_storm_partition_isolation",
                     "partition_view_consistent"):
            assert by_name[name]["pass"], by_name[name]["detail"]

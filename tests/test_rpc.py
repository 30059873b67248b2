"""Cross-host RPC fabric: wire framing, channels, demux failover,
interceptors, domain services, and keyed event forwarding.

Reference behaviors pinned here: ApiDemux round-robin + failover +
waitForChannel backoff (ApiDemux.java:42-110), JWT/tenant interceptors
(JwtServerInterceptor, TenantTokenServerInterceptor.java:53-57), the
near-cached device lookups (CachedDeviceManagementApiChannel.java), and
Kafka's keyed-partition placement at the host boundary
(MicroserviceKafkaProducer.java:106) — two real Instances in one
process, rows crossing "DCN" (localhost TCP) to their owning host.
"""

import json
import socket
import threading
import time

import pytest

from sitewhere_tpu.rpc import (
    ChannelUnavailable,
    HostForwarder,
    RemoteDeviceManagement,
    RpcChannel,
    RpcDemux,
    RpcError,
    RpcServer,
    bind_instance,
    owning_process,
    split_lines,
    wire,
)
from sitewhere_tpu.security.jwt import TokenManagement

from time_limit import WAIT_S, wait_until


# ---------------------------------------------------------------------------
# wire framing
# ---------------------------------------------------------------------------

class TestWire:
    def test_round_trip(self):
        frame = wire.request_frame(
            7, "device.get", {"token": "dev-1"},
            {"authorization": "abc", "tenant": "t1"}, b"\x00\x01binary")
        a, b = socket.socketpair()
        try:
            a.sendall(wire.encode(frame))
            got = wire.read_frame(b)
        finally:
            a.close()
            b.close()
        assert got.request_id == 7
        assert got.method == "device.get"
        assert got.body == {"token": "dev-1"}
        assert got.headers["tenant"] == "t1"
        assert got.attachment == b"\x00\x01binary"
        assert not got.is_response and not got.is_error

    def test_response_and_error_flags(self):
        ok = wire.response_frame(1, {"x": 1})
        err = wire.response_frame(2, {"error": "boom"}, error=True)
        assert ok.is_response and not ok.is_error
        assert err.is_response and err.is_error

    def test_bad_magic_rejected(self):
        a, b = socket.socketpair()
        try:
            a.sendall(b"XXXX" + b"\x00" * 24)
            with pytest.raises(wire.WireError):
                wire.read_frame(b)
        finally:
            a.close()
            b.close()

    def test_undecodable_body_is_wire_error(self):
        # invalid JSON body must surface as WireError (protocol fault →
        # connection drop + failover), never escape as ValueError and
        # kill the reader thread silently
        import struct
        raw = (wire._HEADER.pack(wire.MAGIC, wire.FLAG_RESPONSE, 0, 1)
               + struct.pack(">H", 0)
               + struct.pack(">I", 2) + b"{}"
               + struct.pack(">I", 5) + b"{oops"
               + struct.pack(">I", 0))
        a, b = socket.socketpair()
        try:
            a.sendall(raw)
            with pytest.raises(wire.WireError):
                wire.read_frame(b)
        finally:
            a.close()
            b.close()

    def test_eof_mid_frame(self):
        a, b = socket.socketpair()
        try:
            a.sendall(wire.encode(wire.request_frame(1, "m", None))[:10])
            a.close()
            with pytest.raises(ConnectionError):
                wire.read_frame(b)
        finally:
            b.close()


# ---------------------------------------------------------------------------
# server + channel
# ---------------------------------------------------------------------------

@pytest.fixture()
def server():
    srv = RpcServer(port=0)
    srv.register("echo", lambda ctx, body: body, auth_required=False)
    srv.register("attach",
                 lambda ctx, body: ({"n": len(ctx.attachment)},
                                    ctx.attachment[::-1]),
                 auth_required=False)
    srv.start()
    yield srv
    srv.stop()


class TestServerChannel:
    def test_echo_and_attachment(self, server):
        chan = RpcChannel(server.endpoint)
        body, _ = chan.call("echo", {"hello": "world"})
        assert body == {"hello": "world"}
        body, attach = chan.call("attach", None, attachment=b"abc")
        assert body == {"n": 3}
        assert attach == b"cba"
        chan.close()

    def test_unknown_method_is_rpc_error(self, server):
        chan = RpcChannel(server.endpoint)
        with pytest.raises(RpcError) as exc:
            chan.call("nope", {})
        assert exc.value.error == "not_found"
        chan.close()

    def test_concurrent_calls_multiplex(self, server):
        chan = RpcChannel(server.endpoint)
        results = {}

        def worker(i):
            body, _ = chan.call("echo", {"i": i})
            results[i] = body["i"]

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT_S)
        assert not any(t.is_alive() for t in threads)
        assert results == {i: i for i in range(16)}
        chan.close()

    def test_connection_refused_backoff(self):
        chan = RpcChannel("127.0.0.1:1")   # nothing listens on port 1
        with pytest.raises(ChannelUnavailable):
            chan.call("echo", {})
        # immediately retrying hits the backoff window, not the socket
        with pytest.raises(ChannelUnavailable) as exc:
            chan.call("echo", {})
        assert "backoff" in str(exc.value)
        chan.close()


# ---------------------------------------------------------------------------
# trace-context propagation across the RPC boundary
# ---------------------------------------------------------------------------

class TestTracePropagation:
    """The caller's trace context rides the frame headers; the server
    continues the SAME trace instead of starting a fresh one per call —
    the client/server tracing-interceptor pair."""

    def _server(self, tracer, fn=None):
        srv = RpcServer(port=0, tracer=tracer)
        srv.register("echo", fn or (lambda ctx, body: body),
                     auth_required=False)
        srv.start()
        return srv

    def test_same_trace_id_on_both_sides(self):
        from sitewhere_tpu.runtime.tracing import Tracer

        server_tracer = Tracer(sample_rate=1.0)
        srv = self._server(server_tracer)
        client_tracer = Tracer(sample_rate=1.0)
        try:
            chan = RpcChannel(srv.endpoint)
            trace = client_tracer.trace("forward.batch")
            body, _ = chan.call("echo", {"x": 1}, trace=trace)
            trace.end()
            chan.close()
            assert body == {"x": 1}
            client_spans = client_tracer.recent(10)
            server_spans = server_tracer.recent(10)
            assert [s["name"] for s in client_spans] == ["rpc.client.echo"]
            assert [s["name"] for s in server_spans] == ["rpc.server.echo"]
            # the acceptance criterion: one trace id across the boundary
            assert client_spans[0]["trace_id"] == server_spans[0]["trace_id"]
            # and the server span hangs off the client span
            assert server_spans[0]["parent_id"] == client_spans[0]["span_id"]
            assert server_tracer.joined == 1
        finally:
            srv.stop()

    def test_forced_error_retained_by_tail_sampler_on_both_sides(self):
        """A forced-error call with a 0% head rate: BOTH sides'
        tail samplers keep their half of the trace, same trace_id."""
        from sitewhere_tpu.runtime.tracing import Tracer

        def boom(ctx, body):
            raise ValueError("forced")

        server_tracer = Tracer(sample_rate=0.0, tail_errors=True)
        srv = self._server(server_tracer, fn=boom)
        client_tracer = Tracer(sample_rate=0.0, tail_errors=True)
        try:
            chan = RpcChannel(srv.endpoint)
            trace = client_tracer.trace("forward.batch")
            with pytest.raises(RpcError):
                chan.call("echo", {"x": 1}, trace=trace)
            trace.end()
            chan.close()
            assert server_tracer.retained_tail == 1
            assert client_tracer.retained_tail == 1
            client_spans = client_tracer.recent(10)
            server_spans = server_tracer.recent(10)
            assert client_spans[0]["trace_id"] == server_spans[0]["trace_id"]
            assert server_spans[0]["error"]
        finally:
            srv.stop()

    def test_no_trace_context_starts_fresh_server_trace(self):
        from sitewhere_tpu.runtime.tracing import Tracer

        server_tracer = Tracer(sample_rate=1.0)
        srv = self._server(server_tracer)
        try:
            chan = RpcChannel(srv.endpoint)
            chan.call("echo", {})
            chan.close()
            assert server_tracer.joined == 0
            assert server_tracer.sampled == 1
        finally:
            srv.stop()



class TestInterceptors:
    @pytest.fixture()
    def secured(self):
        tokens = TokenManagement()
        srv = RpcServer(port=0, tokens=tokens)
        srv.register("who", lambda ctx, body: {"user": ctx.username,
                                               "tenant": ctx.tenant})
        srv.register("admin.only", lambda ctx, body: {"ok": True},
                     authority="ROLE_ADMIN")
        srv.register("open", lambda ctx, body: {"ok": True},
                     auth_required=False)
        srv.start()
        yield srv, tokens
        srv.stop()

    def test_jwt_required(self, secured):
        srv, tokens = secured
        chan = RpcChannel(srv.endpoint)
        with pytest.raises(RpcError) as exc:
            chan.call("who", {})
        assert exc.value.error == "unauthorized"
        # open methods skip the interceptor (instance.ping analog)
        body, _ = chan.call("open", {})
        assert body == {"ok": True}
        chan.close()

    def test_jwt_and_tenant_headers_flow(self, secured):
        srv, tokens = secured
        jwt = tokens.mint("alice", ["ROLE_USER"])
        chan = RpcChannel(srv.endpoint, token_provider=lambda: jwt,
                          tenant="acme")
        body, _ = chan.call("who", {})
        assert body == {"user": "alice", "tenant": "acme"}
        chan.close()

    def test_authority_enforced(self, secured):
        srv, tokens = secured
        user = tokens.mint("bob", ["ROLE_USER"])
        admin = tokens.mint("root", ["ROLE_ADMIN"])
        chan = RpcChannel(srv.endpoint, token_provider=lambda: user)
        with pytest.raises(RpcError) as exc:
            chan.call("admin.only", {})
        assert exc.value.error == "forbidden"
        chan.close()
        chan = RpcChannel(srv.endpoint, token_provider=lambda: admin)
        body, _ = chan.call("admin.only", {})
        assert body == {"ok": True}
        chan.close()


# ---------------------------------------------------------------------------
# demux: round-robin, failover, recovery
# ---------------------------------------------------------------------------

class TestDemux:
    def _server(self, tag):
        srv = RpcServer(port=0)
        srv.register("which", lambda ctx, body: {"server": tag},
                     auth_required=False)
        srv.start()
        return srv

    def test_round_robin(self):
        a, b = self._server("a"), self._server("b")
        demux = RpcDemux([a.endpoint, b.endpoint])
        seen = {demux.call("which")[0]["server"] for _ in range(4)}
        assert seen == {"a", "b"}
        demux.close()
        a.stop()
        b.stop()

    def test_failover_when_replica_dies(self):
        a, b = self._server("a"), self._server("b")
        demux = RpcDemux([a.endpoint, b.endpoint])
        demux.call("which")   # connect both eventually
        a.stop()
        # every call still answers, from b
        for _ in range(4):
            assert demux.call("which")[0]["server"] == "b"
        demux.close()
        b.stop()

    def test_all_down_then_wait_for_channel(self):
        srv = self._server("a")
        endpoint = srv.endpoint
        srv.stop()
        demux = RpcDemux([endpoint])
        with pytest.raises(ChannelUnavailable):
            demux.call("which")
        # replica comes back on the same port; wait_for_channel reconnects
        host, port = endpoint.rsplit(":", 1)
        srv2 = RpcServer(host=host, port=int(port))
        srv2.register("which", lambda ctx, body: {"server": "a2"},
                      auth_required=False)
        srv2.start()
        demux.wait_for_channel(timeout_s=10)
        assert demux.call("which")[0]["server"] == "a2"
        demux.close()
        srv2.stop()

    def test_discovery_update_add_remove(self):
        a, b = self._server("a"), self._server("b")
        demux = RpcDemux([a.endpoint])
        assert demux.call("which")[0]["server"] == "a"
        demux.set_endpoints([b.endpoint])   # a removed, b added
        assert demux.endpoints == [b.endpoint]
        assert demux.call("which")[0]["server"] == "b"
        demux.close()
        a.stop()
        b.stop()


class TestWireLimits:
    def test_oversized_attachment_rejected_at_encode(self):
        frame = wire.request_frame(
            1, "m", None, attachment=b"x" * (wire.MAX_ATTACH + 1))
        with pytest.raises(wire.WireError):
            wire.encode(frame)

    def test_expired_token_unauthorized(self):
        tokens = TokenManagement()
        srv = RpcServer(port=0, tokens=tokens)
        srv.register("who", lambda ctx, body: {"user": ctx.username})
        srv.start()
        try:
            expired = tokens.mint("u", ["ROLE_USER"], expiration_min=-1)
            chan = RpcChannel(srv.endpoint,
                              token_provider=lambda: expired)
            with pytest.raises(RpcError) as exc:
                chan.call("who", {})
            assert exc.value.error == "unauthorized"
            chan.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# domain services over the fabric + near-cache
# ---------------------------------------------------------------------------

from sitewhere_tpu.instance import Instance  # noqa: E402
from tests.test_instance import make_config, seed_device  # noqa: E402


@pytest.fixture()
def bound_instance(tmp_path):
    inst = Instance(make_config(tmp_path))
    inst.start()
    srv = RpcServer(port=0, tokens=inst.tokens, tracer=inst.tracer)
    bind_instance(srv, inst)
    srv.start()
    admin = inst.users.authenticate("admin", "password")
    jwt = inst.tokens.mint(admin.username, admin.authorities)
    yield inst, srv, jwt
    srv.stop()
    inst.stop()
    inst.terminate()


class TestDomainServices:
    def test_device_crud_and_events_over_fabric(self, bound_instance):
        inst, srv, jwt = bound_instance
        demux = RpcDemux([srv.endpoint], token_provider=lambda: jwt)
        demux.call("devicetype.create", {"token": "sensor", "name": "S"})
        demux.call("device.create", {"token": "dev-1",
                                     "device_type": "sensor"})
        demux.call("assignment.create", {"device": "dev-1"})
        body, _ = demux.call("device.get", {"token": "dev-1"})
        assert body["token"] == "dev-1"

        # event intake over the binary lane → owner's journaled wire path
        lines = b"\n".join(
            b'{"deviceToken": "dev-1", "type": "Measurement", "request":'
            b' {"name": "temp", "value": %d, "eventDate": 1000}}' % v
            for v in range(8))
        body, _ = demux.call("events.ingest", {"sourceId": "test"},
                             attachment=lines)
        assert body["accepted"] == 8
        inst.dispatcher.flush()
        body, _ = demux.call("events.query", {"deviceToken": "dev-1"})
        assert body["numResults"] == 8

        # state over the fabric
        body, _ = demux.call("state.get", {"deviceToken": "dev-1"})
        assert body["presence_missing"] in (True, False)
        demux.close()

    def test_mutations_need_admin(self, bound_instance):
        inst, srv, jwt = bound_instance
        inst.users.create_granted_authority("ROLE_USER")
        inst.users.create_user(username="viewer", password="pw",
                               authorities=["ROLE_USER"])
        weak = inst.tokens.mint("viewer", ["ROLE_USER"])
        demux = RpcDemux([srv.endpoint], token_provider=lambda: weak)
        with pytest.raises(RpcError) as exc:
            demux.call("device.create", {"token": "x",
                                         "device_type": "sensor"})
        assert exc.value.error == "forbidden"
        demux.close()

    def test_remote_device_management_cache(self, bound_instance):
        inst, srv, jwt = bound_instance
        seed_device(inst, "dev-c")
        demux = RpcDemux([srv.endpoint], token_provider=lambda: jwt)
        remote = RemoteDeviceManagement(demux, cache_ttl_s=60)
        first = remote.get_device("dev-c")
        again = remote.get_device("dev-c")
        assert first == again
        assert remote.hits == 1 and remote.misses == 1
        # write-through invalidation: update → next get refetches
        remote.update_device("dev-c", comments="updated")
        fresh = remote.get_device("dev-c")
        assert fresh["comments"] == "updated"
        assert remote.misses == 2
        # assignment near-cache
        a1 = remote.get_active_assignment("dev-c")
        a2 = remote.get_active_assignment("dev-c")
        assert a1 == a2 and remote.hits == 2
        demux.close()


# ---------------------------------------------------------------------------
# keyed cross-host forwarding (two Instances = two "hosts")
# ---------------------------------------------------------------------------

def _forwarded(fwd):
    """Ship what is pending; the rows forwarded so far."""
    fwd.flush(wait=True)
    return fwd.forwarded_rows


class TestForwarding:
    def test_owning_process_stable(self):
        assert owning_process("dev-1", 4) == owning_process("dev-1", 4)
        owners = {owning_process(f"dev-{i}", 4) for i in range(512)}
        assert owners == {0, 1, 2, 3}   # spreads over all processes

    def test_owning_process_rendezvous_elasticity(self):
        """Growing the fleet P -> P+1 remaps ~1/(P+1) of devices
        (rendezvous hashing; a modulo hash would remap ~P/(P+1)) and
        load stays balanced — including odd P, where a linear weight
        function (raw chained CRC32, the bug this test pins) skewed one
        process to 2× its share."""
        from collections import Counter

        tokens = [f"dev-{i}" for i in range(4000)]
        for P in (2, 3, 4, 5, 7, 8):
            counts = Counter(owning_process(t, P) for t in tokens)
            assert set(counts) == set(range(P))
            share = len(tokens) / P
            for p, n in counts.items():
                assert 0.8 * share < n < 1.2 * share, \
                    f"P={P}: process {p} holds {n} (fair share {share:.0f})"
            moved = sum(owning_process(t, P) != owning_process(t, P + 1)
                        for t in tokens)
            frac = moved / len(tokens)
            ideal = 1 / (P + 1)
            assert ideal / 1.5 < frac < ideal * 1.5, \
                f"P={P}: {frac:.2%} moved (ideal {ideal:.2%})"
            # devices that moved only ever move TO the new process
            for t in tokens:
                a, b = owning_process(t, P), owning_process(t, P + 1)
                assert a == b or b == P

    def test_split_lines_unparseable_stays_local(self):
        payload = (b'{"deviceToken": "d", "type": "Measurement"}\n'
                   b'not json at all\n'
                   b'{"noToken": 1}')
        by_owner = split_lines(payload, 2)
        locals_ = by_owner.get(-1, [])
        assert len(locals_) == 2   # bad line + tokenless line

    def test_flush_wait_never_joins_an_unstarted_sender(self, monkeypatch):
        """A sender enters ``_senders`` and starts under one hold of the
        lock, so ``flush(wait=True)`` racing ``_send_async`` never meets
        "cannot join thread before it is started".  The race is held
        open: the sender's ``start`` waits for the flush to be done, or
        half a second once the flush is shut out by the lock."""
        import types

        from sitewhere_tpu.rpc import forward

        fwd = HostForwarder(None, 0, {0: None, 1: RpcDemux(["127.0.0.1:1"])})
        starting, flushed = threading.Event(), threading.Event()

        class HeldThread(threading.Thread):
            def start(self):
                starting.set()
                flushed.wait(0.5)
                super().start()

        shim = types.ModuleType("threading")
        shim.__dict__.update(vars(threading))
        shim.Thread = HeldThread
        monkeypatch.setattr(forward, "threading", shim)
        caller = threading.Thread(target=fwd._send_async, args=(1,))
        caller.start()
        try:
            assert starting.wait(WAIT_S)
            fwd.flush(wait=True)
        finally:
            flushed.set()
            caller.join(WAIT_S)
        assert not caller.is_alive()
        with fwd._lock:
            assert not fwd._senders and not fwd._active_owners

    @pytest.fixture()
    def two_hosts(self, tmp_path):
        insts, servers = [], []
        for p in range(2):
            inst = Instance(make_config(tmp_path / f"host{p}"))
            inst.start()
            inst.device_management.create_device_type(token="sensor",
                                                      name="S")
            srv = RpcServer(port=0, tokens=inst.tokens, tracer=inst.tracer)
            bind_instance(srv, inst)
            srv.start()
            insts.append(inst)
            servers.append(srv)
        yield insts, servers
        for srv in servers:
            srv.stop()
        for inst in insts:
            inst.stop()
            inst.terminate()

    def test_rows_land_on_owning_host(self, two_hosts):
        insts, servers = two_hosts
        # find tokens owned by each process under the 2-way key hash
        tok0 = next(f"dev-{i}" for i in range(100)
                    if owning_process(f"dev-{i}", 2) == 0)
        tok1 = next(f"dev-{i}" for i in range(100)
                    if owning_process(f"dev-{i}", 2) == 1)
        for inst, tok in ((insts[0], tok0), (insts[1], tok1)):
            inst.device_management.create_device(token=tok,
                                                 device_type="sensor")
            inst.device_management.create_device_assignment(device=tok)

        jwt0 = insts[1].tokens.mint("admin", ["ROLE_ADMIN"])
        demux_to_1 = RpcDemux([servers[1].endpoint],
                              token_provider=lambda: jwt0)
        fwd = HostForwarder(
            insts[0].dispatcher, process_id=0,
            peer_demuxes={0: None, 1: demux_to_1},
            dead_letters=insts[0].dead_letters,
            deadline_ms=10.0)
        fwd.start()
        try:
            # one mixed payload arriving at host 0's frontend
            lines = []
            for tok in (tok0, tok1, tok0, tok1):
                lines.append(
                    b'{"deviceToken": "%s", "type": "Measurement",'
                    b' "request": {"name": "t", "value": 1,'
                    b' "eventDate": 1000}}' % tok.encode())
            accepted = fwd.ingest_payload(b"\n".join(lines))
            assert accepted == 2          # local rows only
            fwd.flush()
            assert wait_until(lambda: fwd.forwarded_rows >= 2)
            assert fwd.forwarded_rows == 2
        finally:
            fwd.stop()
            demux_to_1.close()

        for inst in insts:
            inst.dispatcher.flush()
        d0 = insts[0].identity.device.lookup(tok0)
        d1 = insts[1].identity.device.lookup(tok1)
        insts[0].event_store.flush()
        insts[1].event_store.flush()
        assert len(insts[0].event_store.query(device_id=int(d0))) == 2
        assert len(insts[1].event_store.query(device_id=int(d1))) == 2
        # nothing dead-lettered, nothing misplaced
        assert fwd.dead_lettered == 0

    def test_forwarded_batch_trace_spans_both_hosts(self, two_hosts):
        """The DCN hop is traced end to end: a forwarded batch's
        client span (sender host) and server span (owning host) share
        one trace id — the cross-host half of the acceptance proof."""
        from sitewhere_tpu.runtime.tracing import Tracer

        insts, servers = two_hosts
        tok1 = next(f"dev-{i}" for i in range(100)
                    if owning_process(f"dev-{i}", 2) == 1)
        insts[1].device_management.create_device(token=tok1,
                                                 device_type="sensor")
        insts[1].device_management.create_device_assignment(device=tok1)

        jwt = insts[1].tokens.mint("admin", ["ROLE_ADMIN"])
        demux_to_1 = RpcDemux([servers[1].endpoint],
                              token_provider=lambda: jwt)
        fwd_tracer = Tracer(sample_rate=1.0)
        fwd = HostForwarder(
            insts[0].dispatcher, process_id=0,
            peer_demuxes={0: None, 1: demux_to_1},
            dead_letters=insts[0].dead_letters,
            deadline_ms=10.0, tracer=fwd_tracer)
        fwd.start()
        try:
            fwd.ingest_payload(
                b'{"deviceToken": "%s", "type": "Measurement",'
                b' "request": {"name": "t", "value": 1,'
                b' "eventDate": 1000}}' % tok1.encode())
            fwd.flush()
            assert wait_until(lambda: fwd.forwarded_rows >= 1)
            assert fwd.forwarded_rows == 1
        finally:
            fwd.stop()
            demux_to_1.close()

        sent = [s for s in fwd_tracer.recent(200)
                if s["name"] == "rpc.client.events.ingest"]
        recv = [s for s in insts[1].tracer.recent(200)
                if s["name"] == "rpc.server.events.ingest"]
        assert sent and recv
        shared = {s["trace_id"] for s in sent} & {s["trace_id"] for s in recv}
        assert shared, "no trace id crossed the host boundary"
        # the DCN hop itself is a span (README: "forward.batch"), in the
        # same trace as the client/server legs
        hops = [s for s in fwd_tracer.recent(200)
                if s["name"] == "forward.batch"]
        assert hops and {s["trace_id"] for s in hops} & shared

    def test_config_driven_multihost_instances(self, tmp_path):
        """Two Instances from config alone (rpc.peers + shared jwt
        secret): a TCP protocol source on host 0 receives rows for BOTH
        hosts; each row lands on its owner, end to end."""
        import json as _json
        import socket as _socket
        import struct

        from sitewhere_tpu.ingest.decoders import JsonDecoder
        from sitewhere_tpu.ingest.sources import InboundEventSource, TcpReceiver

        # fixed ports so each peer list can be written before boot
        def free_port():
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            return port

        ports = [free_port(), free_port()]
        peers = [f"127.0.0.1:{p}" for p in ports]
        insts = []
        for p in range(2):
            cfg = make_config(tmp_path / f"host{p}")
            cfg._tree["rpc"] = {
                "server": {"enabled": True, "host": "127.0.0.1",
                           "port": ports[p]},
                "process_id": p, "peers": peers,
                "forward_deadline_ms": 10.0,
            }
            cfg._tree["security"] = {"jwt_secret": "shared-test-secret"}
            inst = Instance(cfg)
            inst.start()
            inst.device_management.create_device_type(token="sensor",
                                                      name="S")
            insts.append(inst)
        assert insts[0].forwarder is not None

        tok0 = next(f"dev-{i}" for i in range(100)
                    if owning_process(f"dev-{i}", 2) == 0)
        tok1 = next(f"dev-{i}" for i in range(100)
                    if owning_process(f"dev-{i}", 2) == 1)
        for inst, tok in ((insts[0], tok0), (insts[1], tok1)):
            inst.device_management.create_device(token=tok,
                                                 device_type="sensor")
            inst.device_management.create_device_assignment(device=tok)

        src = insts[0].add_source(InboundEventSource(
            "tcp", [TcpReceiver(port=0)], JsonDecoder()))
        src.start()
        try:
            port = src.receivers[0].port
            with _socket.create_connection(("127.0.0.1", port)) as s:
                for tok, value in ((tok0, 1.0), (tok1, 2.0),
                                   (tok0, 3.0), (tok1, 4.0)):
                    payload = _json.dumps({
                        "deviceToken": tok, "type": "Measurement",
                        "request": {"name": "t", "value": value,
                                    "eventDate": 1000},
                    }).encode()
                    s.sendall(struct.pack(">I", len(payload)) + payload)
            assert wait_until(
                lambda: _forwarded(insts[0].forwarder) >= 2)
            assert insts[0].forwarder.forwarded_rows == 2
            for inst in insts:
                inst.dispatcher.flush()
                inst.event_store.flush()
            d0 = int(insts[0].identity.device.lookup(tok0))
            d1 = int(insts[1].identity.device.lookup(tok1))
            assert len(insts[0].event_store.query(device_id=d0)) == 2
            assert len(insts[1].event_store.query(device_id=d1)) == 2

            # federated search from host 0 sees BOTH hosts' events
            fed = insts[0].search_providers.get_provider("federated")
            all_events = fed.search()
            assert all_events.total == 4
            remote_only = fed.search(device_token=tok1)
            assert remote_only.total == 2   # rows that live on host 1
            # page_size 0 = unlimited sentinel, same as other providers
            from sitewhere_tpu.services.common import SearchCriteria
            unlimited = fed.search(SearchCriteria(page_size=0))
            assert len(unlimited.results) == unlimited.total == 4

            # cluster topology aggregates the peer over the fabric
            view = insts[0].cluster_topology()
            assert view["local"]["instance"] == "test-instance"
            assert view["peers"]["1"]["devices"] >= 1
            assert view["local"]["forwarding"]["forwarded_rows"] == 2

            # federated command invocation: REST on host 0 invokes a
            # command for host 1's device; the owner runs delivery
            import http.client as _http

            from sitewhere_tpu.web import WebServer

            insts[1].device_management.create_device_command(
                "sensor", token="ping", name="ping")
            a1 = insts[1].device_management.get_active_assignment(tok1)
            ws = WebServer(insts[0], port=0)
            ws.start()
            try:
                conn = _http.HTTPConnection("127.0.0.1", ws.port,
                                            timeout=10)
                jwt = insts[0].tokens.mint("admin", ["ROLE_ADMIN"])
                conn.request(
                    "POST", f"/api/assignments/{a1.token}/invocations",
                    body=_json.dumps({"commandToken": "ping"}),
                    headers={"Authorization": f"Bearer {jwt}"})
                resp = conn.getresponse()
                out = _json.loads(resp.read())
                conn.close()
                assert resp.status == 200 and out["queued"]
                insts[1].dispatcher.flush()
                insts[1].event_store.flush()
                from sitewhere_tpu.schema import EventType
                invs = insts[1].event_store.query(
                    device_id=d1,
                    event_type=int(EventType.COMMAND_INVOCATION))
                assert len(invs) == 1
            finally:
                ws.stop()
        finally:
            for inst in insts:
                inst.stop()
                inst.terminate()

    def test_multihost_requires_shared_secret(self, tmp_path):
        cfg = make_config(tmp_path)
        cfg._tree["rpc"] = {"server": {"enabled": True, "host": "127.0.0.1",
                                       "port": 0},
                            "process_id": 0,
                            "peers": ["127.0.0.1:1", "127.0.0.1:2"]}
        with pytest.raises(ValueError, match="jwt_secret"):
            Instance(cfg)

    def test_durable_spool_survives_restart_and_peer_outage(self, tmp_path):
        """With a data_dir the forwarder write-ahead-spools remote rows:
        an unreachable peer retains them on disk (no dead-letter), and a
        new forwarder over the same spool delivers them once the peer is
        back — the crash-recovery half of at-least-once for the DCN hop."""
        inst = Instance(make_config(tmp_path / "local"))
        inst.start()
        tok = next(f"dev-{i}" for i in range(100)
                   if owning_process(f"dev-{i}", 2) == 1)
        line = (b'{"deviceToken": "%s", "type": "Measurement",'
                b' "request": {"name": "t", "value": 7,'
                b' "eventDate": 1000}}' % tok.encode())
        spool_dir = str(tmp_path / "spool")
        try:
            # phase 1: peer down — rows spool, nothing dead-letters
            down = RpcDemux(["127.0.0.1:1"])
            fwd = HostForwarder(inst.dispatcher, 0, {0: None, 1: down},
                                dead_letters=inst.dead_letters,
                                deadline_ms=5.0, max_retries=1,
                                data_dir=spool_dir)
            assert fwd.durable
            fwd.ingest_payload(line)
            fwd.flush(wait=True)
            assert fwd.dead_lettered == 0
            assert fwd.metrics()["pending"] == 1
            fwd.stop()
            down.close()

            # phase 2: "restart" — peer now up; spool replays on start
            peer = Instance(make_config(tmp_path / "peer"))
            peer.start()
            peer.device_management.create_device_type(token="sensor",
                                                      name="S")
            peer.device_management.create_device(token=tok,
                                                 device_type="sensor")
            peer.device_management.create_device_assignment(device=tok)
            srv = RpcServer(port=0, tokens=peer.tokens)
            bind_instance(srv, peer)
            srv.start()
            jwt = peer.tokens.mint("system", ["ROLE_ADMIN"])
            up = RpcDemux([srv.endpoint], token_provider=lambda: jwt)
            fwd2 = HostForwarder(inst.dispatcher, 0, {0: None, 1: up},
                                 dead_letters=inst.dead_letters,
                                 deadline_ms=5.0, data_dir=spool_dir)
            fwd2.start()
            # the spool commits its tail after the row is counted sent
            assert wait_until(lambda: fwd2.forwarded_rows >= 1
                              and fwd2.metrics()["pending"] == 0)
            assert fwd2.forwarded_rows == 1
            peer.dispatcher.flush()
            peer.event_store.flush()
            d = int(peer.identity.device.lookup(tok))
            assert len(peer.event_store.query(device_id=d)) == 1
            fwd2.stop()
            up.close()
            srv.stop()
            peer.stop()
            peer.terminate()
        finally:
            inst.stop()
            inst.terminate()

    def test_peer_endpoint_live_reload(self, tmp_path):
        """A peer that moves to a new port picks up on config.reload()
        (Consul-watch analog) without restarting the local instance; a
        peer-COUNT change is rejected (ownership would shift)."""
        import json as _json
        import socket as _socket

        def free_port():
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            return port

        p0 = free_port()
        # remote "host 1": one instance, server rebinds ports across the test
        remote = Instance(make_config(tmp_path / "remote"))
        remote.start()
        remote.device_management.create_device_type(token="sensor", name="S")
        tok1 = next(f"dev-{i}" for i in range(100)
                    if owning_process(f"dev-{i}", 2) == 1)
        remote.device_management.create_device(token=tok1,
                                               device_type="sensor")
        remote.device_management.create_device_assignment(device=tok1)
        srv_a = RpcServer(port=0, tokens=remote.tokens)
        bind_instance(srv_a, remote)
        srv_a.start()

        cfg_path = tmp_path / "host0.json"
        base = make_config(tmp_path / "local")._tree

        def write_cfg(peer_ep):
            base["rpc"] = {
                "server": {"enabled": True, "host": "127.0.0.1",
                           "port": p0},
                "process_id": 0,
                "peers": [f"127.0.0.1:{p0}", peer_ep],
                "forward_deadline_ms": 10.0,
            }
            base["security"] = {"jwt_secret": "reload-secret"}
            cfg_path.write_text(_json.dumps(base))

        write_cfg(srv_a.endpoint)
        from sitewhere_tpu.runtime.config import Config
        cfg = Config.load(str(cfg_path), apply_env=False)
        local = Instance(cfg)
        local.start()
        # remote verifies local's service JWTs: same shared secret
        remote.tokens._secret = local.tokens._secret

        line = (b'{"deviceToken": "%s", "type": "Measurement",'
                b' "request": {"name": "t", "value": 1,'
                b' "eventDate": 1000}}' % tok1.encode())
        try:
            local.forwarder.ingest_payload(line)
            local.forwarder.flush(wait=True)
            assert local.forwarder.forwarded_rows == 1

            # peer moves: new server, new port; config file follows
            srv_a.stop()
            srv_b = RpcServer(port=0, tokens=remote.tokens)
            bind_instance(srv_b, remote)
            srv_b.start()
            write_cfg(srv_b.endpoint)
            cfg.reload()
            assert local._peer_demuxes[1].endpoints == [srv_b.endpoint]

            local.forwarder.ingest_payload(line)
            assert wait_until(
                lambda: _forwarded(local.forwarder) >= 2)
            assert local.forwarder.forwarded_rows == 2
            assert local.forwarder.dead_lettered == 0

            # count change is refused: endpoints stay as they were
            base["rpc"]["peers"] = [f"127.0.0.1:{p0}", srv_b.endpoint,
                                    "127.0.0.1:9999"]
            cfg_path.write_text(_json.dumps(base))
            cfg.reload()
            assert len(local._peer_demuxes) == 2
            assert local._peer_demuxes[1].endpoints == [srv_b.endpoint]
            # a pure swap is refused too: same endpoints, different
            # process-id binding = ownership shift
            base["rpc"]["peers"] = [srv_b.endpoint, f"127.0.0.1:{p0}"]
            cfg_path.write_text(_json.dumps(base))
            cfg.reload()
            assert local._peer_demuxes[1].endpoints == [srv_b.endpoint]
            srv_b.stop()
            # terminate deregisters the listener: a reload after
            # teardown must not touch the dead instance's demuxes
            assert local._on_peers_changed in cfg._listeners
        finally:
            local.stop()
            local.terminate()
            remote.stop()
            remote.terminate()
        assert local._on_peers_changed not in cfg._listeners

    def test_down_peer_does_not_accumulate_sender_threads(self, tmp_path):
        """One sender per owner at a time: a down peer being retried must
        not grow a thread pile-up as flush ticks arrive (durable mode
        retains rows, so the owner stays pending for the whole outage)."""
        tok = next(f"dev-{i}" for i in range(100)
                   if owning_process(f"dev-{i}", 2) == 1)
        down = RpcDemux(["127.0.0.1:1"])
        fwd = HostForwarder(None, 0, {0: None, 1: down},
                            deadline_ms=1.0, max_retries=2,
                            data_dir=str(tmp_path))
        try:
            fwd.ingest_payload(
                b'{"deviceToken": "%s", "type": "Measurement",'
                b' "request": {"name": "t", "value": 1}}' % tok.encode())
            for _ in range(50):
                fwd.flush()
            with fwd._lock:
                assert len(fwd._senders) <= 1
            # wait out the in-flight sender: mid-poll the reader position
            # sits past the record until the failure seeks back, so
            # pending only settles once no sender is running
            assert wait_until(lambda: not fwd._senders)
            assert fwd.metrics()["pending"] == 1   # retained, not lost
        finally:
            fwd.stop()
            down.close()

    def test_wrong_secret_peer_dead_letters_as_rejected(self, tmp_path):
        """A peer whose JWT secret doesn't match rejects the forward as
        unauthorized — a NON-retryable rejection, so rows dead-letter
        with the reason recorded instead of spooling forever."""
        peer = Instance(make_config(tmp_path / "peer"))
        peer.start()
        srv = RpcServer(port=0, tokens=peer.tokens)   # peer's own secret
        bind_instance(srv, peer)
        srv.start()
        local = Instance(make_config(tmp_path / "local"))
        local.start()
        try:
            # local mints with ITS secret; peer can't verify it
            jwt = local.tokens.mint("system", ["ROLE_ADMIN"])
            demux = RpcDemux([srv.endpoint], token_provider=lambda: jwt)
            fwd = HostForwarder(local.dispatcher, 0, {0: None, 1: demux},
                                dead_letters=local.dead_letters,
                                deadline_ms=5.0)
            tok = next(f"dev-{i}" for i in range(100)
                       if owning_process(f"dev-{i}", 2) == 1)
            fwd.ingest_payload(
                b'{"deviceToken": "%s", "type": "Measurement",'
                b' "request": {"name": "t", "value": 1}}' % tok.encode())
            fwd.flush(wait=True)
            assert fwd.dead_lettered == 1
            assert fwd.forwarded_rows == 0
            dead = [json.loads(p) for _, p in
                    local.dead_letters.scan(0)]
            rejected = [d for d in dead
                        if d.get("kind") == "undeliverable-forward"]
            assert rejected and "unauthorized" in rejected[0]["reason"]
        finally:
            fwd.stop()
            demux.close()
            srv.stop()
            local.stop()
            local.terminate()
            peer.stop()
            peer.terminate()

    def test_unreachable_peer_dead_letters(self, tmp_path):
        inst = Instance(make_config(tmp_path))
        inst.start()
        try:
            demux = RpcDemux(["127.0.0.1:1"])
            fwd = HostForwarder(
                inst.dispatcher, process_id=0,
                peer_demuxes={0: None, 1: demux},
                dead_letters=inst.dead_letters,
                deadline_ms=5.0, max_retries=1)
            tok = next(f"dev-{i}" for i in range(100)
                       if owning_process(f"dev-{i}", 2) == 1)
            fwd.ingest_payload(
                b'{"deviceToken": "%s", "type": "Measurement",'
                b' "request": {"name": "t", "value": 1}}'
                % tok.encode())
            fwd.flush(wait=True)
            assert fwd.dead_lettered >= 1
            demux.close()
        finally:
            inst.stop()
            inst.terminate()


# ---------------------------------------------------------------------------
# fleet health plane (rpc/health.py) + deadline propagation
# ---------------------------------------------------------------------------

from sitewhere_tpu.rpc import DeadlineExpired, PeerHealthTable, PeerState
from sitewhere_tpu.runtime import faults
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.overload import OverloadShed, OverloadState


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestPeerHealthTable:
    def _table(self, **kw):
        clock = _Clock()
        kw.setdefault("heartbeat_interval_s", 1.0)
        return PeerHealthTable([1], clock=clock, **kw), clock

    def test_silence_escalates_and_heartbeat_recovers(self):
        table, clock = self._table()   # suspect 3s, down 8s, dwell 2s
        assert table.state(1) == PeerState.ALIVE
        clock.advance(3.0)
        table.tick()
        assert table.state(1) == PeerState.SUSPECT
        clock.advance(5.0)
        table.tick()
        assert table.state(1) == PeerState.DOWN
        clock.advance(2.1)             # past the dwell
        table.observe_heartbeat(1)
        assert table.state(1) == PeerState.ALIVE

    def test_failure_streak_escalates_without_silence(self):
        """One-way partition: the peer's heartbeats still arrive but our
        sends fail — the send-failure streak must suspect it anyway,
        and an INCOMING beat must not paper over it (only an answered
        OUTBOUND call proves the path works again)."""
        table, clock = self._table(suspect_failures=3)
        for _ in range(2):
            table.observe_failure(1)
        assert table.state(1) == PeerState.ALIVE
        clock.advance(2.5)             # dwell satisfied
        table.observe_failure(1)
        assert table.state(1) == PeerState.SUSPECT
        # the peer's own beats keep arriving: still parked
        table.observe_heartbeat(1)
        clock.advance(2.5)
        table.tick()
        assert table.state(1) == PeerState.SUSPECT
        # an answered outbound call (a delivered probe) recovers it
        table.observe_alive(1)
        assert table.state(1) == PeerState.ALIVE

    def test_flapping_peer_never_oscillates_faster_than_hysteresis(self):
        """ISSUE acceptance: a peer flapping at the heartbeat period
        can change the table's verdict at most once per hysteresis
        dwell — no park/resume storms (fake clock, bit-exact)."""
        table, clock = self._table(hysteresis_s=2.0)
        transition_times = []
        last = table.state(1)
        # worst-case flap: one beat, then silence past suspect_after,
        # repeatedly, sampled every heartbeat period for 60 "seconds"
        for step in range(60):
            if step % 4 == 0:
                table.observe_heartbeat(1)
            clock.advance(1.0)
            table.tick()
            now_state = table.state(1)
            if now_state != last:
                transition_times.append(clock.t)
                last = now_state
        assert len(transition_times) >= 2      # it did flap
        gaps = [b - a for a, b in zip(transition_times,
                                      transition_times[1:])]
        assert min(gaps) >= 2.0, f"oscillated faster than dwell: {gaps}"
        snap = table.snapshot()["1"]
        assert snap["suppressed_flaps"] > 0    # hysteresis did real work

    def test_probe_pacing_claims_one_slot_per_interval(self):
        table, clock = self._table(probe_interval_s=2.0)
        table.observe_heartbeat(1, overload_state=int(OverloadState.SHEDDING),
                                retry_after_s=5.0)
        assert not table.can_drain(1)
        assert table.probe_due(1)
        assert not table.probe_due(1)          # slot claimed
        clock.advance(2.5)
        assert not table.probe_due(1)          # SHEDDING: retry-after (5s)
        clock.advance(3.0)                     # 5.5s > max(2, 5)
        assert table.probe_due(1)

    def test_owner_pressure_only_when_shedding(self):
        table, clock = self._table()
        assert table.owner_pressure(1) is None
        table.observe_heartbeat(1, overload_state=int(OverloadState.DEGRADED))
        assert table.owner_pressure(1) is None
        table.observe_heartbeat(1, overload_state=int(OverloadState.SHEDDING),
                                retry_after_s=2.0)
        assert table.owner_pressure(1) == (int(OverloadState.SHEDDING), 2.0)

    def test_piggyback_headers_update_overload(self):
        table, clock = self._table()
        table.observe_piggyback(1, {"x-overload": "2",
                                    "x-retry-after": "1.500"})
        assert table.overload_state(1) == 2
        assert table.retry_after(1) == 1.5
        assert not table.can_drain(1)
        table.observe_piggyback(1, {"x-overload": "0"})
        assert table.can_drain(1)

    def test_incarnation_change_is_recorded(self):
        table, clock = self._table()
        table.observe_heartbeat(1, incarnation=7)
        table.observe_heartbeat(1, incarnation=9)
        assert table.snapshot()["1"]["incarnation"] == 9

    def test_departed_peer_gauges_are_pruned(self):
        """Regression: ``forward.peer_state.<p>`` / ``.peer_overload.<p>``
        for a peer removed by set_peers (the apply_membership rebind
        path) used to linger forever — a fleet that churns membership
        accreted one gauge pair per peer that EVER existed, and the
        departed peer's frozen DOWN kept dashboards alerting."""
        registry = MetricsRegistry()
        clock = _Clock()
        table = PeerHealthTable([1, 2], clock=clock,
                                heartbeat_interval_s=1.0, metrics=registry)
        assert "forward.peer_state.2" in registry.names()
        table.set_peers([1, 3])
        names = registry.names()
        # peer 2 left: both its gauges unregister; peer 3 joined
        assert "forward.peer_state.2" not in names
        assert "forward.peer_overload.2" not in names
        assert "forward.peer_state.1" in names
        assert "forward.peer_state.3" in names
        # a full scrape after the churn carries no ghost peers
        from sitewhere_tpu.runtime.metrics import (
            parse_exposition,
            render_openmetrics,
        )

        families = parse_exposition(render_openmetrics(registry))
        assert "forward_peer_state_2" not in families

    def test_forward_metric_names_pass_the_lint(self):
        """Satellite: the forward.* family is a registered, linted
        metric surface — not a dict-only side channel."""
        from sitewhere_tpu.analysis.metric_names import lint_names

        registry = MetricsRegistry()
        fwd = HostForwarder(None, 0, {0: None, 1: RpcDemux(["127.0.0.1:1"])},
                            metrics=registry)
        names = [n for n in registry.names() if n.startswith("forward.")]
        assert "forward.pending_rows" in names
        assert "forward.peer_state.1" in names
        assert lint_names(names) == []


class TestDeadlinePropagation:
    def _server(self, fn):
        srv = RpcServer(port=0)
        srv.register("work.do", fn, auth_required=False)
        srv.start()
        return srv

    def test_expired_call_rejected_before_handler_runs(self):
        """ISSUE acceptance: injected fabric latency burns the budget in
        flight; the server answers deadline_expired WITHOUT executing
        the handler, and the rejection is retryable + distinct from
        peer-down."""
        ran = []
        srv = self._server(lambda c, b: ran.append(1) or {"ok": True})
        chan = RpcChannel(srv.endpoint)
        try:
            with faults.net_injected(srv.endpoint, latency_s=0.4):
                with pytest.raises(DeadlineExpired) as exc:
                    chan.call("work.do", {}, timeout_s=5.0, deadline_s=0.2)
            assert ran == []                       # handler never ran
            assert isinstance(exc.value, RpcError)  # retryable app error
            assert not isinstance(exc.value, ChannelUnavailable)
            # healthy fabric, fresh budget: the same call succeeds
            body, _ = chan.call("work.do", {}, deadline_s=5.0)
            assert body["ok"] and ran == [1]
        finally:
            chan.close()
            srv.stop()

    def test_budget_already_burned_fails_client_side(self):
        srv = self._server(lambda c, b: {"ok": True})
        chan = RpcChannel(srv.endpoint)
        try:
            with pytest.raises(DeadlineExpired):
                chan.call("work.do", {}, deadline_s=0.0)
            assert not chan.connected    # never even dialed
        finally:
            chan.close()
            srv.stop()

    def test_client_timeout_derives_from_budget(self):
        """A propagated 0.3s budget must cap the wait even when the
        caller passed a 30s transport timeout."""
        srv = self._server(lambda c, b: time.sleep(1.2) or {"ok": True})
        chan = RpcChannel(srv.endpoint)
        try:
            t0 = time.monotonic()
            with pytest.raises(ChannelUnavailable):
                chan.call("work.do", {}, timeout_s=30.0, deadline_s=0.3)
            assert time.monotonic() - t0 < 1.0
        finally:
            chan.close()
            srv.stop()

    def test_one_way_partition_executes_but_times_out(self):
        """The half-open link: the request is delivered (the handler
        runs!) but the reply is lost — the caller sees a transport
        fault, the distinct-from-deadline ambiguity a real network
        gives you."""
        ran = []
        srv = self._server(lambda c, b: ran.append(1) or {"ok": True})
        chan = RpcChannel(srv.endpoint)
        try:
            with faults.net_injected(srv.endpoint, drop=1.0, one_way=True):
                with pytest.raises(ChannelUnavailable):
                    chan.call("work.do", {}, timeout_s=0.4)
            assert wait_until(lambda: ran)
            assert ran == [1]
        finally:
            chan.close()
            srv.stop()

    def test_response_piggyback_reaches_header_listener(self):
        seen = {}
        srv = self._server(lambda c, b: {"ok": True})
        srv.overload_provider = lambda: (2, 3.5)
        chan = RpcChannel(srv.endpoint, header_listener=seen.update)
        try:
            chan.call("work.do", {})
            assert seen["x-overload"] == "2"
            assert float(seen["x-retry-after"]) == 3.5
        finally:
            chan.close()
            srv.stop()


class _DownDemux:
    """Fake peer demux: every call is a transport failure."""

    def __init__(self):
        self.calls = 0
        self._lock = threading.Lock()

    def call(self, *a, **kw):
        with self._lock:
            self.calls += 1
        raise ChannelUnavailable("fake peer down")


class _ShedDemux:
    """Fake peer demux: admission refuses everything until healed."""

    def __init__(self):
        self.calls = 0
        self.accepted = []
        self.shedding = True
        self._lock = threading.Lock()

    def call(self, method, body=None, attachment=b"", **kw):
        with self._lock:
            self.calls += 1
            if self.shedding:
                raise RpcError("overloaded", "telemetry shed in SHEDDING",
                               {"x-overload": "2", "x-retry-after": "0.5"})
            lines = [l for l in attachment.split(b"\n") if l]
            self.accepted.extend(lines)
            return {"accepted": len(lines)}, b""


class _AcceptDemux:
    """Fake peer demux: accepts everything, records the lines."""

    def __init__(self):
        self.accepted = []
        self._lock = threading.Lock()

    def call(self, method, body=None, attachment=b"", **kw):
        if method != "events.ingest":
            return {}, b""
        with self._lock:
            lines = [l for l in attachment.split(b"\n") if l]
            self.accepted.extend(lines)
            return {"accepted": len(lines)}, b""


class _CollectorDispatcher:
    """Dispatcher stub: records locally-ingested wire lines."""

    def __init__(self):
        self.lines = []
        self._lock = threading.Lock()

    def ingest_wire_lines(self, payload, source_id="wire",
                          raise_on_decode_error=False):
        lines = [l for l in payload.split(b"\n") if l.strip()]
        with self._lock:
            self.lines.extend(lines)
        return len(lines)


def _wait_senders(fwd, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        with fwd._lock:
            if not fwd._senders:
                return
        time.sleep(0.01)
    raise AssertionError("senders did not quiesce")


def _line_for(owner, n_processes, value):
    tok = next(f"dev-{i}" for i in range(200)
               if owning_process(f"dev-{i}", n_processes) == owner)
    return (b'{"deviceToken": "%s", "type": "Measurement",'
            b' "request": {"name": "t", "value": %d,'
            b' "eventDate": 1000}}' % (tok.encode(), value))


class TestHealthGatedForwarding:
    def _health(self, clock, **kw):
        kw.setdefault("heartbeat_interval_s", 1.0)
        kw.setdefault("hysteresis_s", 0.0)
        kw.setdefault("suspect_failures", 1)
        return PeerHealthTable([1], clock=clock, **kw)

    def test_unhealthy_peer_probes_are_paced_not_a_retry_storm(
            self, tmp_path):
        """ISSUE acceptance: a SUSPECT peer's sender parks the spool and
        sends ONE paced probe per interval — send attempts stay bounded
        no matter how often the flusher ticks."""
        clock = _Clock()
        down = _DownDemux()
        fwd = HostForwarder(None, 0, {0: None, 1: down},
                            max_retries=1, data_dir=str(tmp_path),
                            metrics=MetricsRegistry(),
                            health=self._health(clock, probe_interval_s=2.0),
                            heartbeat_interval_s=0)
        try:
            fwd.ingest_payload(_line_for(1, 2, 1))
            fwd.flush()
            _wait_senders(fwd)
            assert fwd.health.state(1) == PeerState.SUSPECT
            after_first = down.calls
            assert after_first >= 1
            # flusher storm with the probe clock FROZEN: at most the one
            # already-claimed slot may still fire; no pile-up
            for _ in range(30):
                fwd.flush()
                _wait_senders(fwd)
            assert down.calls <= after_first + 1
            # clock advances past the probe interval: exactly one more
            clock.advance(2.5)
            mid = down.calls
            for _ in range(10):
                fwd.flush()
                _wait_senders(fwd)
            assert mid < down.calls <= mid + 1
            assert fwd.metrics()["pending"] == 1   # retained, never lost
            assert fwd.dead_lettered == 0
        finally:
            fwd.stop()

    def test_shed_peer_rows_park_then_drain_on_recovery(self, tmp_path):
        """An overloaded (SHEDDING) owner's rows park in the spool; the
        paced probe redelivers once it recovers and the spool drains —
        zero dead letters for rows the owner was always going to take."""
        clock = _Clock()
        shed = _ShedDemux()
        fwd = HostForwarder(None, 0, {0: None, 1: shed},
                            data_dir=str(tmp_path),
                            metrics=MetricsRegistry(),
                            health=self._health(clock, probe_interval_s=1.0),
                            heartbeat_interval_s=0)
        try:
            fwd.ingest_payload(_line_for(1, 2, 1))
            fwd.flush()
            _wait_senders(fwd)
            # the shed marked the peer's overload state off the error
            # frame's piggyback headers
            assert fwd.health.overload_state(1) == int(OverloadState.SHEDDING)
            assert not fwd.health.can_drain(1)
            assert fwd.metrics()["pending"] == 1
            assert fwd.dead_lettered == 0
            shed.shedding = False
            clock.advance(5.0)        # probe slot opens
            fwd.flush()
            _wait_senders(fwd)
            assert len(shed.accepted) == 1
            assert fwd.metrics()["pending"] == 0
            assert fwd.forwarded_rows == 1
        finally:
            fwd.stop()

    def test_memory_mode_shed_rows_buffer_then_forward_shed_kind(self):
        """Satellite: memory-mode overload-shed rows are NOT
        dead-lettered as 'peer unreachable' — they buffer under the
        retention bound, and a bound-forced drop dead-letters with the
        replayable forward-shed kind (hex payload, like intake-shed)."""
        from sitewhere_tpu.runtime.resilience import CollectingSink

        clock = _Clock()
        shed = _ShedDemux()
        sink = CollectingSink()
        dispatcher = _CollectorDispatcher()
        remote_line = _line_for(1, 2, 201)
        # retention bound fits exactly two remote lines; the third drops
        bound = 2 * (len(remote_line) + 1) + 4
        fwd = HostForwarder(dispatcher, 0, {0: None, 1: shed},
                            dead_letters=sink,
                            metrics=MetricsRegistry(),
                            health=self._health(clock, probe_interval_s=1.0),
                            heartbeat_interval_s=0,
                            max_retained_bytes=bound)
        # mixed local+remote payloads (the gateway-bulk shape): the edge
        # gate never fires, the remote share parks behind the shed owner
        fwd.ingest_payload(_line_for(0, 2, 101) + b"\n" + remote_line)
        fwd.flush()
        _wait_senders(fwd)
        assert fwd.metrics()["pending"] == 1      # retained, not dead
        assert len(sink) == 0
        # two more shed batches overflow the retention bound
        for v in (202, 203):
            clock.advance(5.0)
            fwd.ingest_payload(
                _line_for(0, 2, v - 100) + b"\n" + _line_for(1, 2, v))
            fwd.flush()
            _wait_senders(fwd)
        kinds = [d["kind"] for d in sink.records]
        assert kinds and set(kinds) == {"forward-shed"}
        dropped = sink.records[0]
        assert bytes.fromhex(dropped["payload"])  # replayable (hex) payload
        assert dropped["state"] == "SHEDDING"
        # every local row was ingested in place, every remote row is
        # either retained or audited as forward-shed: no silent loss
        assert len(dispatcher.lines) == 3
        retained = fwd.metrics()["pending"]
        dropped_rows = sum(
            bytes.fromhex(d["payload"]).count(b"\n") + 1
            for d in sink.records)
        assert retained + dropped_rows == 3
        # stop() in memory mode audits still-parked rows as replayable
        # forward-shed records — they die with the process, but never
        # silently
        fwd.stop()
        stop_rows = sum(
            bytes.fromhex(d["payload"]).count(b"\n") + 1
            for d in sink.records)
        assert stop_rows == 3
        assert {d["kind"] for d in sink.records} == {"forward-shed"}

    def test_stop_aborts_sender_backoff_promptly(self):
        """Satellite: sender retry backoff waits on the stop event —
        stop() returns promptly instead of waiting out ~2s sleeps."""
        down = _DownDemux()
        fwd = HostForwarder(None, 0, {0: None, 1: down},
                            max_retries=6,       # 0.1+0.2+...+2.0 ≈ 3.5s
                            metrics=MetricsRegistry(),
                            heartbeat_interval_s=0)
        fwd.start()
        fwd.ingest_payload(_line_for(1, 2, 1))
        fwd.flush()                     # sender enters its backoff loop
        time.sleep(0.15)
        t0 = time.monotonic()
        fwd.stop()
        assert time.monotonic() - t0 < 1.5

    def test_edge_refusal_reflects_remote_owner_overload(self):
        """ISSUE layer 3: a purely remote-owned telemetry payload whose
        owner advertises SHEDDING is refused with the OWNER's hint —
        the receiving transport turns that into 429 / 5.03 / pause."""
        clock = _Clock()
        fwd = HostForwarder(_CollectorDispatcher(), 0,
                            {0: None, 1: _AcceptDemux()},
                            metrics=MetricsRegistry(),
                            health=self._health(clock),
                            heartbeat_interval_s=0)
        fwd.health.observe_heartbeat(
            1, overload_state=int(OverloadState.SHEDDING), retry_after_s=4.0)
        with pytest.raises(OverloadShed) as exc:
            fwd.ingest_payload(_line_for(1, 2, 1))
        assert exc.value.retry_after_s == 4.0
        assert exc.value.state == OverloadState.SHEDDING
        assert fwd.metrics()["pending"] == 0      # nothing buffered
        # a CRITICAL-looking payload is never gated: the owner's own
        # admission decides (alerts are never shed)
        tok = next(f"dev-{i}" for i in range(200)
                   if owning_process(f"dev-{i}", 2) == 1)
        alert = (b'{"deviceToken": "%s", "type": "Alert", "request":'
                 b' {"type": "hot", "level": "warning", "eventDate": 1000}}'
                 % tok.encode())
        fwd.ingest_payload(alert)                 # no raise
        assert fwd.metrics()["pending"] == 1
        # mixed local+remote payloads forward too (spool absorbs)
        mixed = _line_for(0, 2, 7) + b"\n" + _line_for(1, 2, 8)
        fwd.ingest_payload(mixed)
        # recovery clears the gate
        fwd.health.observe_heartbeat(1, overload_state=0)
        fwd.ingest_payload(_line_for(1, 2, 9))
        fwd.stop()

    def test_heartbeat_learns_peer_overload_end_to_end(self, tmp_path):
        """The fleet.heartbeat loop against a real bound instance: the
        sender's table converges on the peer's forced overload state,
        then recovers."""
        inst = Instance(make_config(tmp_path))
        inst.start()
        srv = RpcServer(port=0, tokens=inst.tokens)
        bind_instance(srv, inst)
        srv.start()
        if inst.overload is not None:
            srv.overload_provider = lambda: (int(inst.overload.state),
                                             inst.overload.retry_after())
        jwt = inst.tokens.mint("system", ["ROLE_ADMIN"])
        demux = RpcDemux([srv.endpoint], token_provider=lambda: jwt)
        fwd = HostForwarder(None, 0, {0: None, 1: demux},
                            metrics=MetricsRegistry(),
                            heartbeat_interval_s=0.05)
        fwd.start()
        try:
            inst.overload.force(OverloadState.SHEDDING, reason="test")
            assert wait_until(lambda: fwd.health.overload_state(1)
                              == int(OverloadState.SHEDDING))
            assert fwd.health.state(1) == PeerState.ALIVE
            inst.overload.force(OverloadState.NORMAL, reason="test-done")
            assert wait_until(lambda: fwd.health.overload_state(1) == 0)
        finally:
            fwd.stop()
            demux.close()
            srv.stop()
            inst.stop()
            inst.terminate()


class TestMembershipUnderTraffic:
    def test_route_remote_rejects_stale_generation(self):
        fwd = HostForwarder(_CollectorDispatcher(), 0,
                            {0: None, 1: _AcceptDemux()},
                            metrics=MetricsRegistry(),
                            heartbeat_interval_s=0)
        with fwd._lock:
            gen = fwd._member_gen
        assert fwd._route_remote({}, gen)          # current gen: accepted
        with fwd._lock:
            fwd._member_gen += 1
        assert not fwd._route_remote({}, gen)      # stale: caller recomputes
        fwd.stop()

    def test_flapping_membership_under_concurrent_ingest_loses_nothing(
            self):
        """Satellite: apply_membership while ingest threads hammer the
        forwarder — every row lands at exactly one destination (no
        loss, no double-ownership), and rows ingested after the final
        map settle at their final owners."""
        dispatcher = _CollectorDispatcher()
        demux_a, demux_b, demux_c = (_AcceptDemux(), _AcceptDemux(),
                                     _AcceptDemux())
        maps = [
            {0: None, 1: demux_a, 2: demux_b},
            {0: None, 1: demux_a, 2: demux_b, 3: demux_c},
        ]
        fwd = HostForwarder(dispatcher, 0, dict(maps[0]),
                            metrics=MetricsRegistry(),
                            deadline_ms=2.0,
                            heartbeat_interval_s=0)
        fwd.start()
        n_threads, per_thread = 4, 40
        stop_flap = threading.Event()

        def ingest(tid):
            for i in range(per_thread):
                value = tid * 1000 + i
                # unique value marks the row across every destination
                fwd.ingest_payload(
                    b'{"deviceToken": "dev-%d", "type": "Measurement",'
                    b' "request": {"name": "t", "value": %d,'
                    b' "eventDate": 1000}}' % (value % 64, value))

        def flap():
            i = 0
            while not stop_flap.is_set():
                fwd.apply_membership(dict(maps[i % 2]))
                i += 1
            fwd.apply_membership(dict(maps[0]))    # final map: 3 processes

        threads = [threading.Thread(target=ingest, args=(t,))
                   for t in range(n_threads)]
        flapper = threading.Thread(target=flap)
        flapper.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        stop_flap.set()
        flapper.join(timeout=30)
        # rows ingested AFTER the final membership: ownership must be
        # computed under the final 3-process map, never a stale one
        tail_marker = 999_999
        fwd.ingest_payload(
            b'{"deviceToken": "dev-1", "type": "Measurement",'
            b' "request": {"name": "t", "value": %d,'
            b' "eventDate": 1000}}' % tail_marker)
        fwd.flush(wait=True)
        fwd.stop()

        import re as _re

        def values(lines):
            return [int(_re.search(rb'"value": (\d+)', l).group(1))
                    for l in lines]

        placed = {
            "local": values(dispatcher.lines),
            "a": values(demux_a.accepted),
            "b": values(demux_b.accepted),
            "c": values(demux_c.accepted),
        }
        want = {t * 1000 + i for t in range(n_threads)
                for i in range(per_thread)} | {tail_marker}
        got = [v for vs in placed.values() for v in vs]
        missing = want - set(got)
        assert not missing, f"lost rows: {sorted(missing)[:10]}"
        # exactly-once across DESTINATIONS: a row may never be accepted
        # by two different owners (memory-mode requeue is move, not copy)
        from collections import Counter as _Counter

        dup = {v for dest, vs in placed.items()
               for v in vs
               if sum(v in set(ovs) for ovs in placed.values()) > 1}
        assert not dup, f"double-owned rows: {sorted(dup)[:10]}"
        counts = _Counter(got)
        repeats = {v: c for v, c in counts.items() if c > 1}
        assert not repeats, f"duplicated rows: {list(repeats.items())[:10]}"
        # the tail row landed where the FINAL map says it belongs
        owner = owning_process("dev-1", 3)
        dest = {0: "local", 1: "a", 2: "b"}[owner]
        assert tail_marker in placed[dest]

"""Checkpoint/resume + journal replay: the crash-recovery contract.

Round-2 verdict item #3.  The reference keeps its model durable in MongoDB
(``MongoDeviceManagement.java``) and stream position in Kafka committed
offsets (``MicroserviceKafkaConsumer.java:94,116-139``); a restarted
service resumes where it left off and redelivers uncommitted records
(at-least-once).  These tests kill an instance (no clean stop) and prove a
fresh instance on the same data_dir restores devices/assignments/users/
tenants/rules/zones/DeviceState and replays uncommitted journal records.
"""

import json

import numpy as np
import pytest

from sitewhere_tpu.instance import Instance
from sitewhere_tpu.runtime.config import Config


def _cfg(tmp_path, **over):
    doc = {
        "instance": {"id": "ckpt-test", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 128, "registry_capacity": 256,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "checkpoint": {"interval_s": 0},  # explicit saves only
        "registration": {"default_device_type": "sensor"},
    }
    doc.update(over)
    return Config(doc, apply_env=False)


def _payload(token, value, ts):
    return json.dumps({
        "deviceToken": token,
        "type": "Measurement",
        "request": {"name": "temp", "value": value, "eventDate": ts},
    }).encode()


def _ingest_json(inst, token, value, ts):
    from sitewhere_tpu.ingest.decoders import JsonDecoder

    payload = _payload(token, value, ts)
    inst.dispatcher.ingest(JsonDecoder()(payload)[0], payload=payload)


def test_kill_and_restart_restores_model_and_replays(tmp_path):
    # --- first life -------------------------------------------------------
    a = Instance(_cfg(tmp_path))
    a.start()
    dm = a.device_management
    dm.create_device_type(token="sensor", name="Sensor")
    for i in range(20):
        dm.create_device(token=f"d-{i}", device_type="sensor")
        dm.create_device_assignment(device=f"d-{i}")
    a.users.create_user(username="operator", password="pw12345",
                        first_name="Op", last_name="Erator")
    a.tenants.create_tenant(token="acme", name="Acme",
                            auth_token="acme-auth-token")
    a.rules.create_rule(mtype="temp", op=0, threshold=90.0,
                        alert_type="overheat", token="r-hot")
    dm.create_area_type(token="site", name="Site")
    dm.create_area(token="plant", area_type="site", name="Plant")
    dm.create_zone(token="z-1", area="plant", bounds=[
        [0.0, 0.0], [10.0, 0.0], [10.0, 10.0], [0.0, 10.0]],
        alert_type="breach")

    # processed + committed traffic
    _ingest_json(a, "d-3", 21.5, 1_753_800_100)
    a.dispatcher.flush()
    a.dispatcher.flush()
    events_before = a.event_store.total_events
    assert events_before >= 1
    committed = a.dispatcher.journal_reader.committed
    assert committed == a.ingest_journal.end_offset  # quiescent commit ran

    # snapshot, then CRASH: journal two more payloads that never reach the
    # pipeline (the crash window between Journal.append and egress)
    a.checkpointer.save()
    a.ingest_journal.append(_payload("d-4", 99.5, 1_753_800_200))
    a.ingest_journal.append(_payload("d-5", 12.0, 1_753_800_201))
    a.ingest_journal.close()
    a.dead_letters.close()
    del a  # no stop(), no final checkpoint — simulated kill

    # --- second life ------------------------------------------------------
    b = Instance(_cfg(tmp_path))
    assert b.restored
    b.start()
    try:
        # model survived
        assert b.device_management.get_device("d-3") is not None
        assert b.device_management.get_active_assignment("d-3") is not None
        assert any(u.username == "operator" for u in b.users.list_users())
        assert any(t.token == "acme" for t in b.tenants.list_tenants())
        assert b.rules.get_rule("r-hot").threshold == 90.0
        assert b.device_management.get_zone("z-1") is not None

        # identity handles stayed dense + aligned with the restored mirror
        import numpy as np

        reg = b.mirror.publish_registry()
        d3 = b.identity.device.lookup("d-3")
        assert d3 >= 0 and bool(np.asarray(reg.active)[d3])

        # DeviceState survived (d-3's event from the first life)
        row = b.device_state.get_device_state("d-3")
        assert row["last_event_ts_s"] == 1_753_800_100

        # uncommitted journal records replayed (at-least-once): d-4 fired
        # the threshold rule, d-5 was a normal measurement
        b.dispatcher.flush()
        b.dispatcher.flush()
        assert b.event_store.total_events >= events_before + 2
        assert b.device_state.get_device_state("d-4")["last_event_ts_s"] == \
            1_753_800_200
        snap = b.dispatcher.metrics_snapshot()
        assert snap["threshold_alerts"] >= 1  # replayed d-4 @ 99.5 > 90

        # replay advanced + committed the offset at quiescence
        assert b.dispatcher.journal_reader.committed == \
            b.ingest_journal.end_offset
    finally:
        b.stop()
        b.terminate()


def test_clean_stop_checkpoints_and_restart_is_lossless(tmp_path):
    a = Instance(_cfg(tmp_path))
    a.start()
    a.device_management.create_device_type(token="sensor", name="Sensor")
    a.device_management.create_device(token="dev-a", device_type="sensor")
    a.device_management.create_device_assignment(device="dev-a")
    _ingest_json(a, "dev-a", 33.0, 1_753_800_300)
    a.stop()  # flush + final checkpoint
    a.terminate()
    stored = a.event_store.total_events

    b = Instance(_cfg(tmp_path))
    assert b.restored
    b.start()
    try:
        assert b.device_management.get_device("dev-a") is not None
        assert b.device_state.get_device_state("dev-a")["last_event_ts_s"] \
            == 1_753_800_300
        # nothing to replay after a clean stop — no duplicate events
        b.dispatcher.flush()
        assert b.event_store.total_events == stored
    finally:
        b.stop()
        b.terminate()


def test_periodic_checkpointer_runs(tmp_path):
    import time

    cfg = _cfg(tmp_path, checkpoint={"interval_s": 0.1})
    a = Instance(cfg)
    a.start()
    try:
        deadline = time.monotonic() + 5.0
        while a.checkpointer.last_saved_at is None \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert a.checkpointer.last_saved_at is not None
        assert a.checkpointer.generation >= 0
    finally:
        a.stop()
        a.terminate()


def test_torn_save_keeps_previous_generation(tmp_path):
    """A crash mid-save must leave the previous manifest usable."""
    import os

    a = Instance(_cfg(tmp_path))
    a.start()
    a.device_management.create_device_type(token="sensor", name="Sensor")
    a.device_management.create_device(token="dev-x", device_type="sensor")
    a.checkpointer.save()
    gen = a.checkpointer.generation

    # simulate a torn next save: stray tmp + newer-generation files with no
    # manifest swap
    ckdir = a.checkpointer.dir
    open(os.path.join(ckdir, f"stores-{gen + 1:08d}.pkl.tmp.999"), "wb").close()
    open(os.path.join(ckdir, f"stores-{gen + 1:08d}.pkl"), "wb").close()
    a.ingest_journal.close()
    a.dead_letters.close()
    del a

    b = Instance(_cfg(tmp_path))
    assert b.restored
    assert b.checkpointer.generation == gen
    assert b.device_management.get_device("dev-x") is not None
    b.terminate()


def test_kill_and_restart_on_mesh_restores_sharded_state(tmp_path):
    """Durability × distribution: the same kill-and-restart contract must
    hold when the pipeline runs the shard_map step over the mesh — the
    checkpoint gathers sharded tensors to host, and the restored state is
    re-placed with mesh shardings by the dispatcher's first step."""
    cfg = _cfg(tmp_path, pipeline={
        "width": 128, "registry_capacity": 256, "mtype_slots": 4,
        "deadline_ms": 5.0, "n_shards": 8})
    a = Instance(cfg)
    a.start()
    try:
        dm = a.device_management
        dm.create_device_type(token="sensor", name="Sensor")
        for i in range(16):
            dm.create_device(token=f"d-{i}", device_type="sensor")
            dm.create_device_assignment(device=f"d-{i}")
        _ingest_json(a, "d-3", 21.5, 1_753_800_100)
        a.dispatcher.flush()
        a.dispatcher.flush()
        events_before = a.event_store.total_events
        assert events_before >= 1
        a.checkpointer.save()
        # crash window: journaled but never processed
        a.ingest_journal.append(_payload("d-7", 33.0, 1_753_800_200))
    finally:
        a.ingest_journal.close()
        a.dead_letters.close()
        del a  # simulated kill

    b = Instance(cfg)
    assert b.restored
    b.start()
    try:
        assert b.device_management.get_device("d-3") is not None
        # state tensor restored AND usable by the sharded step
        assert b.device_state.get_device_state("d-3")["last_event_ts_s"] \
            == 1_753_800_100
        b.dispatcher.flush()
        b.dispatcher.flush()
        # the uncommitted record replayed through the SHARDED step
        assert b.event_store.total_events >= events_before + 1
        assert b.device_state.get_device_state("d-7")["last_event_ts_s"] \
            == 1_753_800_200
        # step state ends up placed across the full mesh
        st = b.device_state.current
        assert len(st.last_event_ts_s.sharding.device_set) == 8
    finally:
        b.stop()
        b.terminate()


def test_dead_letter_retention_at_checkpoint(tmp_path):
    """Checkpoint-time dead-letter retention keeps only the newest N
    records (segment-granular, like Kafka topic retention)."""
    cfg = _cfg(tmp_path, dead_letters={"retain_records": 4})
    a = Instance(cfg)
    # tiny segments so several records span multiple segments
    a.dead_letters.segment_bytes = 128
    a.start()
    try:
        for i in range(30):
            a.dead_letters.append_json(
                {"kind": "failed-decode", "source": f"s{i}",
                 "payload": "00" * 16})
        end = a.dead_letters.end_offset
        a.checkpointer.save()
        listed = a.list_dead_letters(limit=100)
        # everything still listable is in the retained tail; the oldest
        # records are gone (segment-granular: at LEAST records below the
        # last whole segment under the cut are dropped)
        assert listed and listed[-1]["offset"] == end - 1
        assert listed[0]["offset"] > 0
        assert len(listed) < 30
    finally:
        a.stop()
        a.terminate()


def test_zone_trim_survives_restore(tmp_path):
    """z_hi (the published ZoneTable's pow2 trim bound) must persist:
    a restored instance with zones beyond the trim floor must keep
    firing their geofences."""
    from tests.test_instance import make_config

    inst = Instance(make_config(tmp_path))
    inst.start()
    dm = inst.device_management
    dm.create_device_type(token="sensor", name="S")
    dm.create_area_type(token="at", name="AT")
    dm.create_area(token="area", area_type="at", name="A")
    n_zones = 12  # beyond the pow2 trim floor of 8
    for i in range(n_zones):
        dm.create_zone(token=f"z-{i}", area="area", name=f"Z{i}",
                       bounds=[(0.0, 0.0), (0.0, 10.0), (10.0, 10.0),
                               (10.0, 0.0)])
    inst.checkpointer.save()
    inst.stop()
    inst.terminate()

    inst2 = Instance(make_config(tmp_path))
    inst2.start()
    try:
        zones = inst2.mirror.publish_zones()
        import numpy as np

        assert zones.capacity >= n_zones
        assert int(np.asarray(zones.active).sum()) == n_zones
    finally:
        inst2.stop()
        inst2.terminate()


@pytest.mark.skipif(
    __import__("sitewhere_tpu.native", fromlist=["load_swwire"])
    .load_swwire() is None, reason="native toolchain unavailable")
def test_replay_columnar_fast_path_matches_scalar_semantics(tmp_path):
    """Journal replay takes the C columnar lane for strict-measurement
    payloads and falls back to the scalar decoder for anything else —
    in particular a request carrying ``metadata.tenant`` must keep its
    tenant routing (the strict scanner bails on unknown request keys,
    so the fast path can never see such a payload)."""
    a = Instance(_cfg(tmp_path))
    a.start()
    dm = a.device_management
    dm.create_device_type(token="sensor", name="Sensor")
    for i in range(4):
        dm.create_device(token=f"d-{i}", device_type="sensor")
        dm.create_device_assignment(device=f"d-{i}")
    a.tenants.create_tenant(token="acme", name="Acme",
                            auth_token="acme-auth")
    a.dispatcher.flush()
    a.checkpointer.save()
    # crash window: journaled but never processed —
    # (1) a multi-line strict measurement payload (columnar replay)
    ndjson = b"\n".join(_payload(f"d-{i}", float(i), 1_753_900_000 + i)
                        for i in range(4))
    a.ingest_journal.append(ndjson)
    # (2) a metadata-tenant payload (must replay via the scalar path)
    meta = json.dumps({
        "deviceToken": "d-0", "type": "Measurement",
        "request": {"name": "temp", "value": 55.0,
                    "eventDate": 1_753_900_100,
                    "metadata": {"tenant": "acme"}},
    }).encode()
    a.ingest_journal.append(meta)
    a.ingest_journal.close()
    a.dead_letters.close()
    del a  # simulated kill

    calls = {"fast": 0}
    from sitewhere_tpu.runtime.dispatcher import PipelineDispatcher

    orig = PipelineDispatcher._replay_columnar

    def counting(self, payload, offset, *tenant):
        out = orig(self, payload, offset, *tenant)
        if out is not None:
            calls["fast"] += 1
        return out

    PipelineDispatcher._replay_columnar = counting
    try:
        from sitewhere_tpu.native import load_swwire

        load_swwire()  # force the build NOW: replay runs inside start(),
        # racing the warmup thread's non-blocking load would skip the
        # fast path on a cold cache
        b = Instance(_cfg(tmp_path))
        b.start()
    finally:
        PipelineDispatcher._replay_columnar = orig
    try:
        b.dispatcher.flush()
        assert calls["fast"] == 1  # the NDJSON payload; meta fell back
        # the 4 strict-measurement rows replayed through the fast path
        assert b.event_store.total_events == 4
        # the metadata payload kept its per-request tenant routing on
        # the scalar path: d-0 has no registration under tenant "acme",
        # so the row was flagged unregistered and dead-lettered — the
        # exact pre-fast-path scalar outcome (a fast path that dropped
        # the metadata would have stored it under the default tenant)
        assert b.dispatcher.totals["unregistered"] >= 1
        assert b.dead_letters.end_offset >= 1
    finally:
        b.stop()
        b.terminate()


# ---------------------------------------------------------------------------
# crash-consistent recovery (ISSUE 12): CRC-framed sections, torn-snapshot
# fallback, version gates, per-component offsets, analytics state
# ---------------------------------------------------------------------------

def test_framed_section_roundtrip_and_corruption(tmp_path):
    """write_framed/read_framed: the CRC framing detects every torn-file
    shape as SnapshotCorrupt (ONE exception type — the restore fallback
    catches exactly it), never a decoder-specific crash."""
    from sitewhere_tpu.runtime.checkpoint import (
        SnapshotCorrupt,
        read_framed,
        write_framed,
    )

    path = str(tmp_path / "x.swsnap")
    write_framed(path, {"component": "x", "version": 3}, b"payload-bytes")
    header, payload = read_framed(path, component="x")
    assert header == {"component": "x", "version": 3}
    assert payload == b"payload-bytes"

    with pytest.raises(SnapshotCorrupt):  # component tag mismatch
        read_framed(path, component="y")

    blob = open(path, "rb").read()
    torn = bytearray(blob)
    torn[-1] ^= 0xFF                       # bit rot in the payload
    open(path, "wb").write(bytes(torn))
    with pytest.raises(SnapshotCorrupt):
        read_framed(path)

    open(path, "wb").write(blob[: len(blob) // 2])  # truncated write
    with pytest.raises(SnapshotCorrupt):
        read_framed(path)

    open(path, "wb").write(b"not a snapshot at all")
    with pytest.raises(SnapshotCorrupt):
        read_framed(path)

    with pytest.raises(SnapshotCorrupt):   # missing file
        read_framed(str(tmp_path / "gone.swsnap"))


def test_torn_generation_falls_back_to_previous_complete(tmp_path):
    """A newer generation whose stores section is bit-rotted must be
    DETECTED (CRC) and abandoned: restore comes up on the previous
    complete generation instead of crashing or half-hydrating."""
    import os

    a = Instance(_cfg(tmp_path))
    a.start()
    a.device_management.create_device_type(token="sensor", name="Sensor")
    a.device_management.create_device(token="dev-old", device_type="sensor")
    a.checkpointer.save()
    gen_good = a.checkpointer.generation
    a.device_management.create_device(token="dev-new", device_type="sensor")
    a.checkpointer.save()
    gen_torn = a.checkpointer.generation
    assert gen_torn == gen_good + 1

    # bit-rot the newer generation's stores section mid-file
    stores = os.path.join(a.checkpointer.dir,
                          f"stores-{gen_torn:08d}.swsnap")
    blob = bytearray(open(stores, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(stores, "wb").write(bytes(blob))
    a.ingest_journal.close()
    a.dead_letters.close()
    del a  # simulated kill

    b = Instance(_cfg(tmp_path))
    assert b.restored
    assert b.checkpointer.restored_generation == gen_good
    assert b.device_management.get_device("dev-old") is not None
    # dev-new was only in the torn generation — re-derivable, not
    # resurrected from a corrupt file
    from sitewhere_tpu.services.common import EntityNotFound

    with pytest.raises(EntityNotFound):
        b.device_management.get_device("dev-new")
    b.terminate()


def test_unsupported_section_version_skips_not_crashes(tmp_path):
    """A section whose schema/version tag no longer matches what the
    provider speaks is SKIPPED with a log line — the rest of the
    generation restores and boot completes (never a mid-boot raise on a
    stale pickle)."""
    import os

    from sitewhere_tpu.runtime.checkpoint import read_framed, write_framed

    a = Instance(_cfg(tmp_path))
    a.start()
    a.device_management.create_device_type(token="sensor", name="Sensor")
    a.device_management.create_device(token="dev-a", device_type="sensor")
    a.analytics.register({
        "kind": "window", "name": "w-mean", "mtype": "temp",
        "agg": "mean", "op": "gt", "threshold": 5.0, "windowS": 60})
    a.checkpointer.save()
    gen = a.checkpointer.generation

    # rewrite the analytics section claiming a future schema version
    path = os.path.join(a.checkpointer.dir,
                        f"analytics-{gen:08d}.swsnap")
    header, payload = read_framed(path, component="analytics")
    header["version"] = 99
    write_framed(path, header, payload)
    a.ingest_journal.close()
    a.dead_letters.close()
    del a  # simulated kill

    b = Instance(_cfg(tmp_path))
    assert b.restored  # the generation itself is fine
    b.start()
    try:
        # stores restored; the version-mismatched analytics section was
        # skipped (its queries are gone, to re-register — not a crash)
        assert b.device_management.get_device("dev-a") is not None
        assert b.analytics.list_queries() == []
        # the skipped section must not anchor the replay floor
        assert "analytics" not in b.checkpointer.restored_offsets
    finally:
        b.stop()
        b.terminate()


def _analytics_cfg(tmp_path, name):
    return _cfg(tmp_path, instance={
        "id": name, "data_dir": str(tmp_path / name)})


def _register_window_query(inst):
    inst.analytics.register({
        "kind": "window", "name": "hot-mean", "mtype": "temp",
        "agg": "mean", "op": "gt", "threshold": 20.0, "windowS": 60})


def _wire_payload(k, width=16):
    lines = []
    for r in range(width):
        i = k * width + r
        lines.append(json.dumps({
            "deviceToken": f"d-{i % 4}", "type": "Measurement",
            "request": {"name": "temp", "value": float(i % 50),
                        "eventDate": 1_753_810_000 + i},
        }))
    return "\n".join(lines).encode()


def _query_states(inst):
    with inst.analytics._lock:
        return {name: e.compiled.export_state()
                for name, e in inst.analytics._queries.items()}


def test_analytics_state_restored_equals_uninterrupted(tmp_path):
    """Golden restored≡uninterrupted: kill with an open tumbling window
    mid-flight, restart, replay — the restored operator state must be
    BIT-IDENTICAL to a control instance that saw the same rows without
    interruption (the tentpole's analytics-equivalence hinge)."""
    def seed(inst):
        dm = inst.device_management
        dm.create_device_type(token="sensor", name="Sensor")
        for i in range(4):
            dm.create_device(token=f"d-{i}", device_type="sensor")
            dm.create_device_assignment(device=f"d-{i}")
        _register_window_query(inst)

    # control: both payloads, uninterrupted
    c = Instance(_analytics_cfg(tmp_path, "control"))
    c.start()
    seed(c)
    c.dispatcher.ingest_wire_lines(_wire_payload(0), "t")
    c.dispatcher.ingest_wire_lines(_wire_payload(1), "t")
    c.dispatcher.flush()
    c.analytics.drain()
    golden = _query_states(c)
    c.stop()
    c.terminate()

    # victim: payload 0 evaluated + checkpointed; payload 1 journaled
    # but NEVER processed (the crash window), then killed
    a = Instance(_analytics_cfg(tmp_path, "victim"))
    a.start()
    seed(a)
    a.dispatcher.ingest_wire_lines(_wire_payload(0), "t")
    a.dispatcher.flush()
    a.analytics.drain()
    a.checkpointer.save()
    # quiesced save: the conservative committed fallback (1) is sound —
    # the provider drained its queue, so everything below it is applied
    assert a.checkpointer._manifest()["offsets"]["analytics"] == 1
    a.ingest_journal.append(_wire_payload(1))
    a.ingest_journal.close()
    a.dead_letters.close()
    del a  # simulated kill

    b = Instance(_analytics_cfg(tmp_path, "victim"))
    assert b.restored
    b.start()  # replays payload 1 through the pipeline into analytics
    try:
        assert [q["query"]["name"] for q in b.analytics.list_queries()] \
            == ["hot-mean"]
        b.dispatcher.flush()
        b.analytics.drain()
        restored = _query_states(b)
        assert set(restored) == set(golden)
        for name in golden:
            for field, arr in golden[name].items():
                np.testing.assert_array_equal(
                    restored[name][field], arr,
                    err_msg=f"{name}.{field} diverged after recovery")
    finally:
        b.stop()
        b.terminate()


def test_analytics_replay_floor_skips_fully_applied_records(tmp_path):
    """A quiesced snapshot's floor covers record 0 entirely: the
    restart replays nothing below it, re-derives nothing, duplicates
    nothing — state and store land exactly where the kill left them."""
    a = Instance(_analytics_cfg(tmp_path, "floor"))
    a.start()
    dm = a.device_management
    dm.create_device_type(token="sensor", name="Sensor")
    for i in range(4):
        dm.create_device(token=f"d-{i}", device_type="sensor")
        dm.create_device_assignment(device=f"d-{i}")
    _register_window_query(a)
    a.dispatcher.ingest_wire_lines(_wire_payload(0), "t")
    a.dispatcher.flush()
    a.analytics.drain()
    a.checkpointer.save()
    a.ingest_journal.close()
    a.dead_letters.close()
    golden = _query_states(a)
    del a  # simulated kill

    b = Instance(_analytics_cfg(tmp_path, "floor"))
    assert b.restored
    # conservative committed as-of (1): record 0 fully applied; its
    # partial-prefix entry rides along and stays inert below the floor
    assert b.analytics.replay_floor == 1
    assert b.analytics._replay_partial == {0: 16}
    b.start()
    try:
        b.dispatcher.flush()
        b.analytics.drain()
        assert b.metrics.counter(
            "analytics.replay_rows_skipped").value == 0
        restored = _query_states(b)
        for name in golden:
            for field, arr in golden[name].items():
                np.testing.assert_array_equal(restored[name][field], arr)
        # and the store did not double-append the replayed rows either
        b.event_store.flush()
        assert b.event_store.total_events == 16
    finally:
        b.stop()
        b.terminate()


def test_analytics_partial_record_prefix_is_row_exact():
    """The review-hardened hinge: one journal record's rows split
    across two plans, snapshot taken BETWEEN the halves — the snapshot
    pairs the state with a per-record applied-prefix count, and replay
    drops exactly that prefix, so the suffix still applies and state
    converges to the uninterrupted run's (never losing the unapplied
    half, never double-counting the applied one)."""
    from sitewhere_tpu.analytics.runner import QueryRunner
    from sitewhere_tpu.runtime.metrics import MetricsRegistry

    def cols(lo, hi):
        n = hi - lo
        return {
            "device_id": np.arange(lo, hi, dtype=np.int32) % 4,
            "ts_s": np.arange(1_753_840_000 + lo, 1_753_840_000 + hi,
                              dtype=np.int64),
            "event_type": np.zeros(n, np.int32),   # MEASUREMENT
            "mtype_id": np.zeros(n, np.int32),
            "value": np.arange(lo, hi, dtype=np.float32),
            "payload_ref": np.zeros(n, np.int32),  # ONE journal record
        }

    def make_runner():
        r = QueryRunner(capacity=8, metrics=MetricsRegistry(),
                        resolve_mtype=lambda name: 0)
        r.register({"kind": "window", "name": "w", "mtype": "temp",
                    "agg": "sum", "op": "gt", "threshold": 1e9,
                    "windowS": 60})
        r.start()
        return r

    # control: all 12 rows of record 0, uninterrupted
    ctrl = make_runner()
    ctrl.submit_live(cols(0, 12), np.ones(12, bool), committed=0)
    ctrl.drain()
    golden = {n: e.compiled.export_state()
              for n, e in ctrl._queries.items()}
    ctrl.stop()

    # victim: only the FIRST half of record 0 applied, then snapshot
    # (exactly what a periodic checkpoint racing a split record sees)
    a = make_runner()
    a.submit_live(cols(0, 8), np.ones(8, bool), committed=0)
    a.drain()
    payload, header = a.snapshot_state()
    a.stop()
    # record 0 never committed → no watermark; the checkpointer stamps
    # its conservative committed offset (0 here) in this case
    assert header["as_of"] is None
    header = dict(header, as_of=0)

    # restore + full-record replay: the 8-row prefix drops, the 4-row
    # suffix applies
    b = make_runner()
    assert b.restore_state(header, payload) == 1
    b.submit_live(cols(0, 12), np.ones(12, bool), committed=0)
    b.drain()
    assert b.metrics.counter("analytics.replay_rows_skipped").value == 8
    restored = {n: e.compiled.export_state()
                for n, e in b._queries.items()}
    b.stop()
    for name in golden:
        for field, arr in golden[name].items():
            np.testing.assert_array_equal(
                restored[name][field], arr,
                err_msg=f"{name}.{field} diverged across a split-record "
                        f"checkpoint boundary")


def test_stop_final_checkpoint_offset_never_leads_journal(tmp_path):
    """Shutdown-ordering audit (ISSUE 12 satellite): Instance.stop runs
    the final save AFTER the dispatcher flush drains ring + egress and
    commits the final offset — so the snapshot's claimed offsets can
    never lead the sealed journal.  Regression-pin the ordering."""
    import os

    a = Instance(_cfg(tmp_path))
    a.start()
    a.device_management.create_device_type(token="sensor", name="Sensor")
    a.device_management.create_device(token="d-0", device_type="sensor")
    a.device_management.create_device_assignment(device="d-0")
    for k in range(3):
        _ingest_json(a, "d-0", float(k), 1_753_820_000 + k)
    a.stop()  # flush + drain + commit, THEN the final save
    a.terminate()

    with open(os.path.join(str(tmp_path / "data"), "checkpoint",
                           "MANIFEST.json")) as f:
        manifest = json.load(f)
    end = a.ingest_journal.end_offset
    # the final snapshot covers the whole sealed journal…
    assert manifest["committed"] == end
    assert manifest["journal_end"] == end
    # …and no component section claims an offset past it
    assert manifest["offsets"]
    for section, off in manifest["offsets"].items():
        assert off <= end, f"{section} as-of {off} leads journal end {end}"

    # restart replays nothing (clean shutdown == nothing uncommitted)
    b = Instance(_cfg(tmp_path))
    assert b.restored
    b.start()
    try:
        assert b.metrics.gauge("recovery.replay_events").value == 0
    finally:
        b.stop()
        b.terminate()


def test_dedup_window_survives_restart(tmp_path):
    """The per-source dedup LRU rides the runtime checkpoint section: a
    restarted instance keeps rejecting alternate ids the window had
    already seen instead of re-admitting them until the LRU refills."""
    from sitewhere_tpu.ingest.decoders import DecodedRequest, RequestKind
    from sitewhere_tpu.ingest.dedup import AlternateIdDeduplicator

    def req(alt):
        return DecodedRequest(kind=RequestKind.MEASUREMENT,
                              device_token="d-0", ts_s=1, alternate_id=alt)

    d = AlternateIdDeduplicator(window=4)
    assert not d.is_duplicate(req("alpha"))
    assert not d.is_duplicate(req("beta"))
    keys = d.export_keys()
    assert len(keys) == 2

    d2 = AlternateIdDeduplicator(window=4)
    d2.import_keys(keys)
    assert d2.is_duplicate(req("alpha")) and d2.is_duplicate(req("beta"))
    assert not d2.is_duplicate(req("gamma"))

    # truncation: only the newest `window` keys survive a smaller window
    d3 = AlternateIdDeduplicator(window=1)
    d3.import_keys(keys)
    assert d3.is_duplicate(req("beta"))       # newest kept
    assert not d3.is_duplicate(req("alpha"))  # aged out by the window


def test_recovery_metrics_exported_on_restore(tmp_path):
    """recovery.restore_s / recovery.replay_s / recovery.replay_events:
    RTO is a measured number on every boot that restored."""
    a = Instance(_cfg(tmp_path))
    a.start()
    a.device_management.create_device_type(token="sensor", name="Sensor")
    a.device_management.create_device(token="d-0", device_type="sensor")
    a.device_management.create_device_assignment(device="d-0")
    _ingest_json(a, "d-0", 1.0, 1_753_830_000)
    a.dispatcher.flush()
    a.checkpointer.save()
    a.ingest_journal.append(_payload("d-0", 2.0, 1_753_830_001))
    a.ingest_journal.close()
    a.dead_letters.close()
    del a  # simulated kill

    b = Instance(_cfg(tmp_path))
    assert b.restored
    b.start()
    try:
        gauges = b.metrics.snapshot()["gauges"]
        assert gauges["recovery.restore_s"] > 0
        assert gauges["recovery.replay_events"] == 1
        assert gauges["recovery.replay_s"] > 0
        assert b.checkpointer.restore_s > 0
    finally:
        b.stop()
        b.terminate()


@pytest.mark.parametrize("version", [2, 1])
def test_stores_section_restores_in_both_layouts(tmp_path, version):
    """Version 2 holds each store's containers pickled on their own
    under that store's lock (``{store: bytes}``); a version-1 section
    (``{store: {attr: container}}``, written before) still restores."""
    import os
    import pickle

    from sitewhere_tpu.runtime.checkpoint import read_framed, write_framed

    a = Instance(_cfg(tmp_path))
    a.start()
    a.device_management.create_device_type(token="sensor", name="Sensor")
    a.device_management.create_device(token="dev-a", device_type="sensor",
                                      metadata={"site": "north"})
    a.device_management.create_device_assignment(device="dev-a")
    a.checkpointer.save()
    gen = a.checkpointer.generation
    path = os.path.join(a.checkpointer.dir, f"stores-{gen:08d}.swsnap")
    header, payload = read_framed(path, component="stores")
    stores = pickle.loads(payload)
    assert header["version"] == 2
    assert all(isinstance(v, bytes) for k, v in stores.items()
               if k != "__engines__")
    assert "dev-a" in pickle.loads(stores["device_management"])["devices"]
    if version == 1:
        old = {k: (v if k == "__engines__" else pickle.loads(v))
               for k, v in stores.items()}
        header["version"] = 1
        write_framed(path, header, pickle.dumps(old, protocol=4))
    a.ingest_journal.close()
    a.dead_letters.close()
    del a  # simulated kill

    b = Instance(_cfg(tmp_path))
    assert b.restored
    try:
        dev = b.device_management.get_device("dev-a")
        assert dev is not None and dev.metadata == {"site": "north"}
        assert b.device_management.get_active_assignment("dev-a") is not None
    finally:
        b.terminate()


def test_a_torn_store_inside_the_stores_section_fails_the_generation(
        tmp_path):
    """The inner pickles are read while the generation is validated, not
    while it is applied: an unreadable one falls back to the previous
    generation like any torn section."""
    import os
    import pickle

    from sitewhere_tpu.runtime.checkpoint import read_framed, write_framed

    a = Instance(_cfg(tmp_path))
    a.start()
    a.device_management.create_device_type(token="sensor", name="Sensor")
    a.device_management.create_device(token="dev-a", device_type="sensor")
    a.checkpointer.save()
    good = a.checkpointer.generation
    a.device_management.create_device(token="dev-b", device_type="sensor")
    a.checkpointer.save()
    gen = a.checkpointer.generation
    path = os.path.join(a.checkpointer.dir, f"stores-{gen:08d}.swsnap")
    header, payload = read_framed(path, component="stores")
    stores = pickle.loads(payload)
    stores["device_management"] = stores["device_management"][:-7]
    write_framed(path, header, pickle.dumps(stores, protocol=4))
    a.ingest_journal.close()
    a.dead_letters.close()
    del a

    b = Instance(_cfg(tmp_path))
    try:
        assert b.restored and b.checkpointer.restored_generation == good
        assert b.device_management.get_device("dev-a") is not None
    finally:
        b.terminate()


def test_identity_file_is_what_json_dump_wrote(tmp_path):
    """``IdentityMap.save`` encodes in one pass of the C encoder; the
    bytes are ``json.dump``'s, and load into a fresh map round-trips."""
    import io

    from sitewhere_tpu.ids import IdentityMap

    im = IdentityMap()
    for i in range(300):
        im.device.mint(f"d-{i}")
    im.device.free("d-7")
    im.mtype.mint("temp")
    path = str(tmp_path / "identity.json")
    im.save(path)
    want = io.StringIO()
    json.dump({n: s.to_dict() for n, s in im.spaces.items()}, want)
    with open(path) as f:
        assert f.read() == want.getvalue()
    back = IdentityMap.load(path)
    assert back.device.lookup("d-299") == im.device.lookup("d-299")
    assert back.device.lookup("d-7") == -1 and back.device.mint("new") == 7

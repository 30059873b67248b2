"""Event store: buffered writes, durable chunks, indexed queries, restart.

Reference parity targets: DeviceEventBuffer flush semantics, the
Cassandra-style denormalized index queries, and Kafka-offset-style restart
recovery (events survive process restart).
"""

import os
import threading
import time

import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.schema import EventType
from sitewhere_tpu.services.common import EntityNotFound, SearchCriteria
from sitewhere_tpu.services.event_store import (
    COLUMNS,
    EventStore,
    event_id,
    split_event_id,
)

from time_limit import wait_until


def make_cols(n, *, device=None, area=None, etype=int(EventType.MEASUREMENT), ts0=1000):
    cols = {}
    for name, dtype in COLUMNS:
        if name == "received_s":
            continue
        cols[name] = np.full(n, NULL_ID if np.issubdtype(dtype, np.integer) else 0.0, dtype)
    cols["device_id"] = np.asarray(device if device is not None else np.arange(n), np.int32)
    cols["tenant_id"] = np.zeros(n, np.int32)
    cols["event_type"] = np.full(n, etype, np.int32)
    cols["ts_s"] = np.arange(ts0, ts0 + n, dtype=np.int32)
    cols["value"] = np.linspace(0, 1, n).astype(np.float32)
    if area is not None:
        cols["area_id"] = np.full(n, area, np.int32)
    return cols


def test_append_flush_and_get(tmp_path):
    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    added = store.append_columns(make_cols(10))
    assert added == 10
    assert store.total_events == 10
    n = store.flush()
    assert n == 10
    rec = store.get_event(event_id(0, 3))
    assert rec.device_id == 3
    assert rec.received_s > 0
    with pytest.raises(EntityNotFound):
        store.get_event(event_id(5, 0))


def test_mask_and_row_threshold_autoflush(tmp_path):
    store = EventStore(str(tmp_path), flush_rows=16, flush_interval_s=10)
    mask = np.zeros(10, np.bool_)
    mask[:4] = True
    store.append_columns(make_cols(10), mask=mask)
    assert store.total_events == 4
    store.append_columns(make_cols(20))  # crosses flush_rows → auto-seal
    assert len(store._chunks) == 1
    assert store._chunks[0].n == 24


def test_query_indexes_and_time_range(tmp_path):
    store = EventStore(str(tmp_path), flush_rows=1000, flush_interval_s=10)
    store.append_columns(
        make_cols(50, device=np.full(50, 7, np.int32), area=3, ts0=1000)
    )
    store.append_columns(
        make_cols(50, device=np.full(50, 8, np.int32), area=4, ts0=2000,
                  etype=int(EventType.LOCATION))
    )
    res = store.query(device_id=7)
    assert res.total == 50
    # newest-first ordering
    assert res.results[0].ts_s == 1049
    res = store.query(area_id=4, event_type=int(EventType.LOCATION))
    assert res.total == 50
    res = store.query(SearchCriteria(start_s=1040, end_s=2005))
    assert res.total == 10 + 6
    res = store.query(SearchCriteria(page=2, page_size=30), device_id=7)
    assert len(res.results) == 20
    assert res.total == 50
    assert store.query(device_id=999).total == 0


def test_restart_recovers_chunks(tmp_path):
    store = EventStore(str(tmp_path))
    store.append_columns(make_cols(25))
    store.flush()
    eid = event_id(0, 24)

    store2 = EventStore(str(tmp_path))
    assert store2.total_events == 25
    assert store2.get_event(eid).ts_s == 1024
    # New writes continue the chunk sequence.
    store2.append_columns(make_cols(5, ts0=5000))
    store2.flush()
    assert split_event_id(store2.query(SearchCriteria(page_size=1)).results[0].event_id)[0] == 1


def test_add_single_event(tmp_path):
    store = EventStore(str(tmp_path))
    rec = store.add_event(
        device_id=5, tenant_id=0, event_type=int(EventType.ALERT),
        ts_s=1234, alert_code=9, alert_level=2,
    )
    assert rec.alert_code == 9
    # Visible while still buffered (no forced flush per REST create)...
    assert store.get_event(rec.event_id).device_id == 5
    assert store.query(device_id=5).total == 1
    # ...and the id stays correct across interleaved appends + the seal.
    store.append_columns(make_cols(10))
    store.flush()
    assert store.get_event(rec.event_id).device_id == 5


def test_buffered_rows_visible_to_query(tmp_path):
    store = EventStore(str(tmp_path), flush_rows=10_000, flush_interval_s=10)
    store.append_columns(make_cols(10, device=np.full(10, 3, np.int32)))
    assert not store._chunks  # nothing sealed yet
    assert store.query(device_id=3).total == 10


def test_oversized_buffer_splits_into_chunks(tmp_path, monkeypatch):
    import sitewhere_tpu.services.event_store as es

    monkeypatch.setattr(es, "_ROW_BITS", 2)  # max 3 rows per chunk
    store = EventStore(str(tmp_path), flush_rows=10_000, flush_interval_s=10)
    store.append_columns(make_cols(10))
    assert store.flush() == 10
    assert len(store._chunks) == 4
    assert store.total_events == 10
    assert store.query().total == 10


def test_interval_flusher_thread(tmp_path):
    import time

    store = EventStore(str(tmp_path), flush_rows=10_000, flush_interval_s=0.05)
    store.initialize()
    store.start()
    try:
        store.append_columns(make_cols(3))
        assert wait_until(lambda: store._chunks)
        assert store._chunks[0].n == 3
    finally:
        store.stop()


def test_iter_chunks_for_analytics(tmp_path):
    store = EventStore(str(tmp_path))
    store.append_columns(make_cols(10))
    store.flush()
    store.append_columns(make_cols(10, ts0=2000))
    chunks = list(store.iter_chunks())
    assert len(chunks) == 2
    assert chunks[1]["ts_s"][0] == 2000


class TestRetention:
    def test_prune_older_than_drops_whole_chunks(self, tmp_path):
        import os as _os

        store = EventStore(str(tmp_path), flush_rows=4,
                           flush_interval_s=999.0)
        # two sealed chunks: old (ts 100..103) and new (ts 5000..5003)
        for base in (100, 5000):
            for i in range(4):
                store.add_event(device_id=1, tenant_id=0, event_type=0,
                                ts_s=base + i, mtype_id=0, value=1.0)
            store.flush()
        assert store.total_events == 8
        n_files = len([f for f in _os.listdir(store.dir)
                       if f.endswith(".npz")])
        assert n_files == 2

        removed = store.prune_older_than(cutoff_s=1000)
        assert removed == 4
        assert store.total_events == 4
        assert len([f for f in _os.listdir(store.dir)
                    if f.endswith(".npz")]) == 1
        # queries only see the surviving chunk
        res = store.query()
        assert all(r.ts_s >= 5000 for r in res.results)
        # a straddling chunk is kept whole
        assert store.prune_older_than(cutoff_s=5002) == 0

        # reopen over the pruned directory resumes cleanly at the next seq
        store2 = EventStore(str(tmp_path), flush_rows=4,
                            flush_interval_s=999.0)
        assert store2.total_events == 4
        store2.add_event(device_id=1, tenant_id=0, event_type=0,
                         ts_s=6000, mtype_id=0, value=1.0)
        store2.flush()
        assert store2.total_events == 5

    def test_checkpoint_prunes_committed_journal(self, tmp_path):
        """With journal.prune_after_checkpoint, a snapshot reclaims
        ingest-journal segments below the pipeline's committed offset —
        and a restart over the pruned dir still restores and accepts."""
        import json as _json

        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.runtime.config import Config

        def cfg():
            return Config({
                "instance": {"id": "ret", "data_dir": str(tmp_path / "d")},
                "pipeline": {"width": 64, "registry_capacity": 1024,
                             "mtype_slots": 4, "deadline_ms": 5.0,
                             "n_shards": 1},
                "presence": {"scan_interval_s": 3600.0,
                             "missing_after_s": 1800},
                "journal": {"fsync_every": 0, "segment_bytes": 256,
                            "prune_after_checkpoint": True},
                "checkpoint": {"interval_s": 3600.0},
            }, apply_env=False)

        inst = Instance(cfg())
        inst.start()
        inst.device_management.create_device_type(token="s", name="S")
        inst.device_management.create_device(token="dev", device_type="s")
        inst.device_management.create_device_assignment(device="dev")
        for i in range(30):     # tiny segments -> several rotations
            inst.dispatcher.ingest_wire_lines(_json.dumps({
                "deviceToken": "dev", "type": "Measurement",
                "request": {"name": "t", "value": i, "eventDate": 1000 + i},
            }).encode())
        inst.dispatcher.flush()
        import os as _os

        jdir = inst.ingest_journal.dir
        before = len([f for f in _os.listdir(jdir) if f.endswith(".log")])
        assert before > 1
        inst.checkpointer.save()
        after = len([f for f in _os.listdir(jdir) if f.endswith(".log")])
        assert after < before
        inst.stop()
        inst.terminate()

        # restart over the pruned journal: restore + new intake both work
        inst2 = Instance(cfg())
        inst2.start()
        assert inst2.device_management.get_device("dev") is not None
        inst2.dispatcher.ingest_wire_lines(_json.dumps({
            "deviceToken": "dev", "type": "Measurement",
            "request": {"name": "t", "value": 99, "eventDate": 2000},
        }).encode())
        inst2.dispatcher.flush()
        inst2.event_store.flush()
        d = int(inst2.identity.device.lookup("dev"))
        assert len(inst2.event_store.query(device_id=d)) == 31
        inst2.stop()
        inst2.terminate()

    def test_seqs_never_regress_after_full_prune(self, tmp_path):
        """Retention can delete EVERY chunk; a restart must still issue
        fresh chunk seqs — a reissued event id would silently resolve to
        an unrelated newer event (ids embed the chunk seq)."""
        store = EventStore(str(tmp_path), flush_rows=2,
                           flush_interval_s=999.0)
        store.add_event(device_id=1, tenant_id=0, event_type=0,
                        ts_s=100, mtype_id=0, value=1.0)
        store.flush()
        old_id = store.query().results[0].event_id
        assert store.prune_older_than(cutoff_s=10_000) == 1
        assert store.total_events == 0

        store2 = EventStore(str(tmp_path), flush_rows=2,
                            flush_interval_s=999.0)
        store2.add_event(device_id=2, tenant_id=0, event_type=0,
                         ts_s=20_000, mtype_id=0, value=2.0)
        store2.flush()
        new_id = store2.query().results[0].event_id
        assert new_id != old_id            # seq did not regress
        import pytest as _pytest

        from sitewhere_tpu.services.common import EntityNotFound
        with _pytest.raises(EntityNotFound):
            store2.get_event(old_id)      # pruned id stays dead

    def test_legacy_store_without_marker_survives_full_prune(self, tmp_path):
        """Stores created before the next-seq marker existed get one
        written at load time — otherwise an idle store fully pruned by
        retention would restart seqs at 0 on the next boot."""
        import os

        store = EventStore(str(tmp_path), flush_rows=2,
                           flush_interval_s=999.0)
        store.add_event(device_id=1, tenant_id=0, event_type=0,
                        ts_s=100, mtype_id=0, value=1.0)
        store.flush()
        old_id = store.query().results[0].event_id
        os.unlink(os.path.join(str(tmp_path), "events", "next-seq"))  # legacy

        store2 = EventStore(str(tmp_path), flush_rows=2,
                            flush_interval_s=999.0)
        assert os.path.exists(os.path.join(str(tmp_path), "events", "next-seq"))
        # idle store: prune everything WITHOUT any flush writing a marker
        assert store2.prune_older_than(cutoff_s=10_000) == 1

        store3 = EventStore(str(tmp_path), flush_rows=2,
                            flush_interval_s=999.0)
        store3.add_event(device_id=2, tenant_id=0, event_type=0,
                         ts_s=20_000, mtype_id=0, value=2.0)
        store3.flush()
        assert store3.query().results[0].event_id != old_id

    def test_stale_marker_backfilled_at_load(self, tmp_path):
        """Crash between a chunk seal and its marker write leaves the
        marker below the chunk-derived seq; load must bring it forward or
        a later full prune regresses seqs."""
        import os

        store = EventStore(str(tmp_path), flush_rows=2,
                           flush_interval_s=999.0)
        store.add_event(device_id=1, tenant_id=0, event_type=0,
                        ts_s=100, mtype_id=0, value=1.0)
        store.flush()
        marker = os.path.join(str(tmp_path), "events", "next-seq")
        with open(marker, "w") as f:
            f.write("0")  # simulate the pre-seal marker surviving a crash

        store2 = EventStore(str(tmp_path), flush_rows=2,
                            flush_interval_s=999.0)
        with open(marker) as f:
            assert int(f.read()) == 1  # backfilled from the chunk scan
        assert store2.prune_older_than(cutoff_s=10_000) == 1

        store3 = EventStore(str(tmp_path), flush_rows=2,
                            flush_interval_s=999.0)
        assert store3._next_seq == 1  # marker, not the (empty) chunk scan


def test_query_matches_naive_reference(tmp_path):
    """The zone-map/Bloom/early-stop query must return exactly what a
    naive filter+full-sort does — same page rows, same order, same total
    — over chunks with heavily overlapping time ranges (the degraded
    path) and equal-timestamp ties crossing chunk boundaries."""
    import numpy as np

    from sitewhere_tpu.services.common import SearchCriteria

    rng = np.random.default_rng(7)
    store = EventStore(str(tmp_path), flush_rows=1_000_000_000)
    rows = []
    for chunk in range(6):
        n = 500
        dev = rng.integers(0, 40, n).astype(np.int32)
        # coarse timestamps force ties within AND across chunks
        ts = rng.integers(1000, 1020, n).astype(np.int32)
        ns = rng.integers(0, 3, n).astype(np.int32)
        cols = dict(
            device_id=dev, tenant_id=(dev % 3),
            event_type=rng.integers(0, 3, n).astype(np.int32),
            ts_s=ts, ts_ns=ns,
            mtype_id=(dev % 4), value=rng.random(n).astype(np.float32),
            lat=np.zeros(n, np.float32), lon=np.zeros(n, np.float32),
            elevation=np.zeros(n, np.float32),
            alert_code=np.full(n, -1, np.int32),
            alert_level=np.zeros(n, np.int32),
            command_id=np.full(n, -1, np.int32),
            payload_ref=np.full(n, -1, np.int32),
            device_type_id=np.zeros(n, np.int32), assignment_id=dev,
            area_id=(dev % 5), customer_id=(dev % 2), asset_id=(dev % 7),
        )
        store.append_columns(cols)
        store.flush()
        for i in range(n):
            rows.append((int(ts[i]), int(ns[i]), chunk, i,
                         int(dev[i]), int(cols["event_type"][i])))

    def naive(criteria, device_id=None, event_type=None):
        hits = [
            r for r in rows
            if (device_id is None or r[4] == device_id)
            and (event_type is None or r[5] == event_type)
            and (criteria.start_s is None or r[0] >= criteria.start_s)
            and (criteria.end_s is None or r[0] <= criteria.end_s)
        ]
        # newest-first, ties by insertion (chunk, row) order
        hits.sort(key=lambda r: (-(r[0] * 1_000_000_000 + r[1]),
                                 r[2], r[3]))
        lo = (criteria.page - 1) * criteria.page_size
        return ([(r[2], r[3]) for r in hits[lo:lo + criteria.page_size]],
                len(hits))

    cases = [
        (SearchCriteria(page_size=50), {}),
        (SearchCriteria(page=3, page_size=40), {}),
        (SearchCriteria(page=20, page_size=40), {}),
        (SearchCriteria(page_size=25), {"device_id": 7}),
        (SearchCriteria(page_size=25), {"device_id": 7, "event_type": 1}),
        (SearchCriteria(page_size=30, start_s=1005, end_s=1012), {}),
        (SearchCriteria(page_size=30, start_s=1005, end_s=1012),
         {"device_id": 3}),
        (SearchCriteria(page_size=0), {}),  # unlimited sentinel
        (SearchCriteria(page_size=25), {"device_id": 9999}),  # no hits
    ]
    for criteria, filters in cases:
        got = store.query(criteria, **filters)
        want_page, want_total = naive(criteria, **filters)
        assert got.total == want_total, (criteria, filters)
        got_page = [split_event_id(r.event_id) for r in got.results]
        if criteria.page_size > 0:
            assert got_page == want_page, (criteria, filters)
        else:
            assert len(got.results) == want_total


# -- bounded resident set (VERDICT r4 item 5) --------------------------------


def test_restart_reads_only_metadata(tmp_path):
    """Reopening a store must not materialize sealed columns: prune
    metadata persisted at seal time is all a restart touches."""
    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    for i in range(5):
        store.append_columns(make_cols(50, ts0=1000 + i * 50))
        store.flush()
    reopened = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    assert len(reopened._chunks) == 5
    stats = reopened.cache_stats()
    assert stats["loads"] == 0 and stats["bytes"] == 0
    for chunk in reopened._chunks:
        assert chunk._cols is None  # lazy: nothing resident
        assert chunk.bounds is not None and chunk.blooms  # metadata is
    # a query still answers correctly (columns page in on demand)
    res = reopened.query(device_id=7)
    assert res.total == 5
    assert reopened.cache_stats()["loads"] > 0


def test_lru_evicts_under_pressure_and_answers_stay_correct(tmp_path):
    """With a cache far smaller than the data, scans/queries stream
    through the LRU (evictions happen, bytes stay bounded) and results
    match an unbounded store exactly."""
    kw = dict(flush_rows=10_000, flush_interval_s=10)
    small = EventStore(str(tmp_path / "small"), resident_bytes=64 << 10, **kw)
    big = EventStore(str(tmp_path / "big"), **kw)
    for i in range(8):
        cols = make_cols(1000, device=np.arange(1000) % 37,
                         ts0=1000 + i * 1000)
        small.append_columns(cols)
        big.append_columns(cols)
        small.flush()
        big.flush()

    crit = SearchCriteria(page_size=50)
    for kwargs in ({"device_id": 5}, {"event_type": int(EventType.MEASUREMENT)},
                   {"device_id": 11, "mtype_id": NULL_ID}):
        a = small.query(crit, **kwargs)
        b = big.query(crit, **kwargs)
        assert a.total == b.total
        assert [r.event_id for r in a.results] == [
            r.event_id for r in b.results]

    # scan the whole store: the cache must not grow past its budget
    seen = 0
    for cols in small.iter_chunks():
        seen += len(cols["ts_s"])
    assert seen == 8000
    stats = small.cache_stats()
    assert stats["evictions"] > 0
    assert stats["bytes"] <= stats["max_bytes"]


def test_pre_metadata_chunk_format_still_opens(tmp_path):
    """A chunk sealed by an older store (no persisted metadata) opens via
    the rebuild path and then behaves identically (lazy + pruned)."""
    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    store.append_columns(make_cols(64, ts0=5000))
    store.flush()
    # strip the metadata members, simulating the old format
    import os
    fname = [f for f in os.listdir(store.dir) if f.endswith(".npz")][0]
    path = os.path.join(store.dir, fname)
    with np.load(path) as data:
        cols = {k: data[k] for k in data.files if not k.startswith("_")}
    with open(path, "wb") as f:
        np.savez(f, **cols)

    reopened = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    assert len(reopened._chunks) == 1
    chunk = reopened._chunks[0]
    assert chunk._cols is None  # released after the metadata rebuild
    assert chunk.bounds is not None
    res = reopened.query(device_id=3)
    assert res.total == 1
    assert res.results[0].ts_s == 5003


def test_pruned_chunk_leaves_no_cache_residue(tmp_path):
    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    store.append_columns(make_cols(10, ts0=1000))
    store.flush()
    store.append_columns(make_cols(10, ts0=9000))
    store.flush()
    assert store.query(device_id=3).total == 2  # faults columns in
    assert store.cache_stats()["bytes"] > 0
    removed = store.prune_older_than(5000)
    assert removed == 10
    assert all(key[0] != 0 for key in store._cache._od)


def test_iter_chunks_skips_chunk_pruned_mid_scan(tmp_path):
    """Retention unlinking a chunk file between snapshot and read must
    skip that chunk, not kill the scan (lazy-load prune race)."""
    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    for ts0 in (1000, 2000, 9000):
        store.append_columns(make_cols(10, ts0=ts0))
        store.flush()
    gen = store.iter_chunks()
    first = next(gen)  # snapshot taken; chunk 0 materialized
    assert first["ts_s"][0] == 1000
    # retention fires mid-scan: chunks 0 and 1 expire (files unlinked,
    # cache dropped) while the generator still holds the old snapshot
    assert store.prune_older_than(3000) == 20
    rest = list(gen)
    assert len(rest) == 1  # chunk 1 skipped (gone), chunk 2 delivered
    assert rest[0]["ts_s"][0] == 9000


def test_query_retries_on_chunk_pruned_race(tmp_path):
    """A query whose snapshot raced retention retries on a fresh
    snapshot and succeeds."""
    from sitewhere_tpu.services import event_store as mod

    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    store.append_columns(make_cols(10, ts0=1000))
    store.flush()
    store.append_columns(make_cols(10, ts0=9000))
    store.flush()

    real = store._query_once
    calls = []

    def racing(criteria=None, **kw):
        if not calls:
            calls.append(1)
            store.prune_older_than(5000)  # fires "mid-query"
            raise mod._ChunkPruned(0)
        return real(criteria, **kw)

    store._query_once = racing
    res = store.query(device_id=3)
    assert res.total == 1  # old chunk pruned; fresh snapshot answers
    assert res.results[0].ts_s == 9003


def test_get_event_on_vanished_chunk_reports_expired(tmp_path):
    """An id resolving into a chunk whose file vanished mid-lookup
    reports EntityNotFound (expired id), not FileNotFoundError."""
    import os
    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    store.append_columns(make_cols(10, ts0=1000))
    store.flush()
    # simulate the race: file gone + cache dropped, but the chunk still
    # sits in the snapshot get_event takes
    fname = [f for f in os.listdir(store.dir) if f.endswith(".npz")][0]
    os.unlink(os.path.join(store.dir, fname))
    store._cache.drop_seq(0)
    with pytest.raises(EntityNotFound):
        store.get_event(event_id(0, 3))


def test_pre_metadata_upgrade_persists_once(tmp_path):
    """Opening a legacy chunk rebuilds AND persists its metadata, so the
    full-column read happens once, not on every boot."""
    import os
    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    store.append_columns(make_cols(64, ts0=5000))
    store.flush()
    fname = [f for f in os.listdir(store.dir) if f.endswith(".npz")][0]
    path = os.path.join(store.dir, fname)
    with np.load(path) as data:
        cols = {k: data[k] for k in data.files if not k.startswith("_")}
    with open(path, "wb") as f:
        np.savez(f, **cols)  # strip metadata = legacy format

    EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    with np.load(path) as data:  # upgraded in place
        assert "_meta_core" in data.files
        assert "_bloom_device_id" in data.files
    # the next boot takes the metadata-only path: no column loads
    third = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    assert third.cache_stats()["loads"] == 0
    assert third.query(device_id=3).total == 1


def test_cache_rejects_put_after_drop_seq(tmp_path):
    """A column load racing retention must not park dead bytes in the
    LRU after drop_seq ran."""
    from sitewhere_tpu.services.event_store import _ColumnCache
    cache = _ColumnCache(1 << 20)
    cache.put((0, "ts_s"), np.arange(10))
    cache.drop_seq(0)
    cache.put((0, "value"), np.arange(100))  # late arrival: rejected
    assert cache.bytes == 0
    assert cache.get((0, "value")) is None
    cache.put((1, "ts_s"), np.arange(10))  # other seqs unaffected
    assert cache.get((1, "ts_s")) is not None


def test_query_self_heals_externally_deleted_chunk(tmp_path):
    """A chunk file deleted outside retention (disk fault, operator rm)
    must not livelock query(): the store discards the vanished chunk
    and answers from the rest."""
    import os
    store = EventStore(str(tmp_path), flush_rows=100, flush_interval_s=10)
    store.append_columns(make_cols(10, ts0=1000))
    store.flush()
    store.append_columns(make_cols(10, ts0=9000))
    store.flush()
    # delete chunk 0 behind the store's back; it stays in _chunks
    os.unlink(os.path.join(store.dir, "events-0000000000.npz"))
    store._cache.drop_seq(0)
    res = store.query(device_id=3)  # would spin forever without healing
    assert res.total == 1
    assert res.results[0].ts_s == 9003
    assert len(store._chunks) == 1  # vanished chunk discarded


def test_deferred_fsync_settled_by_explicit_flush(tmp_path):
    """Routine seals defer durability; flush(sync=True) settles it.

    The at-least-once premise: chunks need fsync only before the journal
    offset covering their rows commits (the commit gate calls flush()).
    """
    store = EventStore(str(tmp_path), flush_rows=10_000, flush_interval_s=10)
    store.append_columns(make_cols(50))
    store.flush(sync=False)
    # sealed atomically (file exists, readable) but durability deferred
    assert len(store._chunks) == 1
    assert store._unsynced_paths  # chunk + marker pending fsync
    rec = store.get_event(event_id(0, 7))
    assert rec.device_id == 7
    store.flush()  # the commit-gate call
    assert not store._unsynced_paths


def test_started_store_seals_on_flusher_thread(tmp_path):
    """append_columns past flush_rows signals the background flusher
    instead of sealing on the writer thread (egress p99 protection)."""
    store = EventStore(str(tmp_path), flush_rows=16, flush_interval_s=0.05)
    store.start()
    try:
        store.append_columns(make_cols(40))
        assert wait_until(lambda: store._chunks)
        assert store._chunks[0].n == 40
        assert store.total_events == 40
    finally:
        store.stop()
    # stop() runs a sync flush: everything durable
    assert not store._unsynced_paths


def test_prune_settles_marker_before_unlink(tmp_path):
    """Seqs must not regress: prune writes the high-water marker durably
    BEFORE chunk files disappear (boot recovers a stale marker from the
    chunk files themselves — which prune deletes)."""
    store = EventStore(str(tmp_path), flush_rows=10_000, flush_interval_s=10,
                       retention_s=60)
    store.append_columns(make_cols(30, ts0=1000))
    store.flush(sync=False)
    assert store._unsynced_paths
    removed = store.prune_older_than(10_000)
    assert removed == 30
    assert not store._chunks
    # marker no longer pending, and a fresh store resumes past seq 0
    marker = os.path.join(store.dir, "next-seq")
    assert int(open(marker).read()) == 1
    store2 = EventStore(str(tmp_path), flush_rows=10_000)
    assert store2._next_seq == 1


def test_torn_chunk_quarantined_at_boot(tmp_path):
    """A power loss mid-deferred-seal can leave garbage at the canonical
    chunk name (rename lands before the content fsync).  Boot must
    quarantine it and keep going — the rows are journal-covered because
    their offset can only commit after a sync flush."""
    store = EventStore(str(tmp_path), flush_rows=10_000, flush_interval_s=10)
    store.append_columns(make_cols(20))
    store.flush()
    store.append_columns(make_cols(30, ts0=5000))
    store.flush()
    # tear the SECOND chunk: truncated npz, as delayed allocation leaves it
    torn = os.path.join(store.dir, "events-0000000001.npz")
    with open(torn, "wb") as f:
        f.write(b"PK\x03\x04garbage")
    store2 = EventStore(str(tmp_path))
    assert len(store2._chunks) == 1          # healthy chunk loads
    assert store2._chunks[0].n == 20
    assert store2._next_seq == 2             # seq does NOT regress
    assert os.path.exists(torn + ".corrupt")  # quarantined, not deleted
    assert not os.path.exists(torn)
    # the store keeps working past the quarantine
    store2.append_columns(make_cols(5, ts0=9000))
    store2.flush()
    assert store2._chunks[-1].seq == 2


def test_unwritten_chunk_retry_and_sync_refusal(tmp_path, monkeypatch):
    """A failed npz write parks the chunk on the retry list: its rows
    stay readable (columns attached), flush(sync=True) REFUSES (the
    commit gate must not commit past it), and the next flush writes the
    file and detaches."""
    store = EventStore(str(tmp_path), flush_rows=10_000, flush_interval_s=10)
    store.append_columns(make_cols(25))
    real = EventStore._write_chunk_file
    boom = {"n": 0}

    def failing(self, path, cols, chunk, sync=True):
        boom["n"] += 1
        raise OSError("disk full")

    monkeypatch.setattr(EventStore, "_write_chunk_file", failing)
    with pytest.raises(OSError):
        store.flush()  # sync=True: must refuse on the unwritten chunk
    assert boom["n"] == 1
    assert len(store._unwritten) == 1
    # rows are still fully readable from the attached columns
    assert store.total_events == 25
    assert store.get_event(event_id(0, 7)).device_id == 7
    assert store.query(device_id=7).total == 1
    assert not os.path.exists(
        os.path.join(store.dir, "events-0000000000.npz"))

    monkeypatch.setattr(EventStore, "_write_chunk_file", real)
    assert store.flush() == 0  # no NEW rows; retries the parked chunk
    assert not store._unwritten
    assert os.path.exists(
        os.path.join(store.dir, "events-0000000000.npz"))
    # the retried chunk detached and survives a reopen
    store2 = EventStore(str(tmp_path))
    assert store2.total_events == 25
    assert store2.get_event(event_id(0, 7)).device_id == 7


def test_concurrent_flush_prune_read_stress(tmp_path):
    """Writer + background flusher + retention prune + readers hammer
    the two-phase flush concurrently; every surviving row stays
    readable and accounting never goes negative."""
    store = EventStore(str(tmp_path), flush_rows=64, flush_interval_s=0.01,
                       retention_s=10_000)
    store.start()
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            try:
                store.query(SearchCriteria(page_size=5))
                store.total_events
            except Exception as e:  # pragma: no cover - failure surface
                errors.append(e)
                return

    def pruner():
        while not stop.is_set():
            try:
                store.prune_older_than(int(time.time()) - 10_000)
            except Exception as e:  # pragma: no cover
                errors.append(e)
                return
            time.sleep(0.005)

    threads = [threading.Thread(target=reader) for _ in range(2)]
    threads.append(threading.Thread(target=pruner))
    for t in threads:
        t.start()
    try:
        now = int(time.time())
        total_new = 0
        for i in range(60):
            n = 17 + (i % 13)
            if i % 3 == 0:
                # expired rows: the concurrent pruner genuinely removes
                # these chunks WHILE flush phase 2 may be writing them,
                # exercising the pruned-mid-write unlink + the doomed
                # _unwritten filter
                store.append_columns(make_cols(n, ts0=now - 20_000))
            else:
                store.append_columns(make_cols(n, ts0=now))
                total_new += n
        store.flush()
        # drain any expired chunks the racing pruner didn't get to;
        # a chunk that mixed old+new rows straddles the cutoff and is
        # rightly kept whole, so assert on the NEW rows' integrity, not
        # an exact total
        store.prune_older_than(int(time.time()) - 10_000)
        res = store.query(SearchCriteria(start_s=now, page_size=10**6))
        assert res.total == total_new
        assert store.total_events >= total_new
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        store.stop()
    assert not errors, errors

"""Log-structured sharded segment store (sitewhere_tpu/store).

Invariant suite for ISSUE 13: parallel background seal off the hot
path, catalog-governed retention/compaction, packed hot tier, and the
retrospective scan lane — golden live≡retro equivalence through
segments, catalog pruning correctness (zone-map/Bloom
false-negative-free), compaction idempotence, tiering
demotion/promotion round-trips, and the prune-vs-concurrent-seal
regression.
"""

import os
import threading
import time

import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.schema import EventType
from sitewhere_tpu.services.common import EntityNotFound
from sitewhere_tpu.store.segment import (
    COLUMNS,
    COLUMN_NAMES,
    Segment,
    event_id,
    pack_cols,
    split_event_id,
    unpack_cols,
    write_segment_file,
)
from sitewhere_tpu.store.segmented import SegmentStore

M = int(EventType.MEASUREMENT)
A = int(EventType.ALERT)
T0 = 1_753_900_000


def make_cols(n, *, device=None, tenant=None, etype=M, ts0=T0, value=None):
    cols = {}
    for name, dtype in COLUMNS:
        if name == "received_s":
            continue
        cols[name] = np.full(
            n, NULL_ID if np.issubdtype(dtype, np.integer) else 0.0, dtype)
    cols["device_id"] = np.asarray(
        device if device is not None else np.arange(n), np.int32)
    cols["tenant_id"] = np.asarray(
        tenant if tenant is not None else np.zeros(n), np.int32)
    cols["event_type"] = np.full(n, etype, np.int32)
    cols["ts_s"] = np.arange(ts0, ts0 + n, dtype=np.int32)
    cols["value"] = (np.linspace(0, 1, n).astype(np.float32)
                     if value is None else np.asarray(value, np.float32))
    return cols


def make_store(root, *, flush_rows=64, n_shards=4, workers=2,
               hot_bytes=64 << 20, compact_interval_s=0.0, **kw):
    return SegmentStore(
        str(root), flush_rows=flush_rows, flush_interval_s=10.0,
        n_shards=n_shards, seal_workers=workers, hot_bytes=hot_bytes,
        compact_interval_s=compact_interval_s, **kw)


def scan_rows(store, **filters):
    """(device_id, ts_s, value) tuples in scan order."""
    out = []
    for cols in store.iter_chunks(**filters):
        out.extend(zip(cols["device_id"].tolist(),
                       cols["ts_s"].tolist(),
                       np.round(cols["value"], 5).tolist()))
    return out


# ---------------------------------------------------------------------------
# parallel seal off the hot path
# ---------------------------------------------------------------------------


class TestBackgroundSeal:
    def test_append_seals_on_workers_not_caller(self, tmp_path):
        store = make_store(tmp_path, flush_rows=32)
        store.sealer.start()
        try:
            for k in range(8):
                store.append_columns(make_cols(64, ts0=T0 + 64 * k))
            store.flush(sync=True)
        finally:
            store.sealer.stop()
        assert store.total_events == 512
        assert store.sealer.sealed_segments > 0
        # every sealed segment is a durable file the catalog lists
        assert store.verify_catalog() == []

    def test_buffers_grow_on_demand_not_eagerly(self, tmp_path):
        """A huge flush_rows (the benches' 'never auto-seal' idiom)
        must not eagerly allocate gigabytes per shard buffer."""
        store = make_store(tmp_path, flush_rows=1 << 30, n_shards=2)
        store.append_columns(make_cols(10))
        bufs = [b for b in store._open_bufs if b is not None]
        assert bufs
        for b in bufs:
            assert b.alloc <= b.INITIAL_ROWS  # lazy, not cap-sized
        # growth past the initial allocation keeps every row
        store.append_columns(make_cols(9_000))
        store.flush(sync=True)
        assert store.total_events == 9_010

    def test_unstarted_store_still_seals_inline(self, tmp_path):
        store = make_store(tmp_path, flush_rows=16)
        store.append_columns(make_cols(64))
        store.flush(sync=True)
        assert store.total_events == 64
        assert store.verify_catalog() == []

    def test_reads_see_queued_and_buffered_rows(self, tmp_path):
        # with no workers running, filled buffers sit in the seal queue:
        # queries and ids must still resolve (fail-closed visibility)
        store = make_store(tmp_path, flush_rows=16, n_shards=1)
        rec = store.add_event(device_id=3, tenant_id=0, event_type=M,
                              ts_s=T0, mtype_id=1, value=2.5)
        store.append_columns(make_cols(40, ts0=T0 + 1))
        assert store.total_events == 41
        got = store.get_event(rec.event_id)
        assert got.value == pytest.approx(2.5)
        assert store.query(device_id=3).total >= 1
        store.flush(sync=True)
        assert store.get_event(rec.event_id).value == pytest.approx(2.5)

    def test_event_ids_stable_across_background_seal(self, tmp_path):
        store = make_store(tmp_path, flush_rows=8, n_shards=2)
        recs = [store.add_event(device_id=i % 4, tenant_id=0, event_type=M,
                                ts_s=T0 + i, mtype_id=1, value=float(i))
                for i in range(32)]
        store.sealer.start()
        try:
            store.flush(sync=True)
        finally:
            store.sealer.stop()
        for i, rec in enumerate(recs):
            assert store.get_event(rec.event_id).value == float(i)

    def test_flush_contract_restart_recovers(self, tmp_path):
        store = make_store(tmp_path, flush_rows=16)
        store.append_columns(make_cols(100))
        store.flush(sync=True)
        before = sorted(scan_rows(store))
        # restart: catalog rebuilds from segment files + manifest marker
        store2 = make_store(tmp_path, flush_rows=16)
        assert store2.total_events == 100
        assert sorted(scan_rows(store2)) == before
        assert store2.verify_catalog() == []


# ---------------------------------------------------------------------------
# golden live ≡ retro equivalence through segments
# ---------------------------------------------------------------------------


class TestLiveRetroEquivalence:
    def _feed(self, store, batches):
        for cols in batches:
            store.append_columns(cols)

    def _batches(self):
        rng = np.random.default_rng(11)
        batches = []
        for k in range(12):
            n = 48
            dev = rng.integers(0, 16, n).astype(np.int32)
            cols = make_cols(n, device=dev, ts0=T0 + k * n,
                             value=rng.random(n).astype(np.float32) * 50)
            batches.append(cols)
        return batches

    def test_per_device_order_survives_seal_and_compaction(self, tmp_path):
        batches = self._batches()
        live = {}  # device -> [(ts, value)] in arrival order
        for cols in batches:
            for d, t, v in zip(cols["device_id"].tolist(),
                               cols["ts_s"].tolist(),
                               np.round(cols["value"], 5).tolist()):
                live.setdefault(d, []).append((t, v))
        store = make_store(tmp_path, flush_rows=32, n_shards=4,
                           compact_min_rows=128)
        self._feed(store, batches)
        store.flush(sync=True)

        def retro_per_device():
            retro = {}
            for d, t, v in scan_rows(store):
                retro.setdefault(d, []).append((t, v))
            return retro

        assert retro_per_device() == live
        # ...and again through compaction (order_key keeps scan order)
        merged = store.compactor.drain()
        assert merged > 0
        assert retro_per_device() == live
        assert store.verify_catalog() == []
        # ...and across a restart of the compacted store
        store2 = make_store(tmp_path, flush_rows=32, n_shards=4)
        retro2 = {}
        for d, t, v in scan_rows(store2):
            retro2.setdefault(d, []).append((t, v))
        assert retro2 == live

    def test_compiled_query_matches_live_evaluation(self, tmp_path):
        """The H-STREAM claim: ONE compiled operator, fed live batches
        or sealed segments, produces identical matches."""
        from sitewhere_tpu.analytics.query import WindowQuery, compile_query

        batches = self._batches()
        q = WindowQuery(name="w", threshold=25.0, agg="mean", window_s=64)
        live_op = compile_query(q, capacity=16)
        live_matches = []
        for cols in batches:
            live_matches.extend(live_op.eval_cols(cols))
        live_matches.extend(live_op.flush())

        store = make_store(tmp_path, flush_rows=32, n_shards=4,
                           compact_min_rows=128)
        self._feed(store, batches)
        store.flush(sync=True)
        store.compactor.drain()
        retro_op = compile_query(q, capacity=16)
        retro_matches = []
        for cols in store.iter_chunks(event_type=M):
            retro_matches.extend(retro_op.eval_cols(cols))
        retro_matches.extend(retro_op.flush())

        # value rounded like the golden crash harness: float32 window
        # sums accumulate in batch-split order, and live batches split
        # differently than sealed segments (ULP-level drift)
        key = lambda m: (m.device_id, m.start_ts_s, round(m.value, 3))
        assert sorted(map(key, retro_matches)) == \
            sorted(map(key, live_matches))
        assert live_matches  # the workload produces real matches


# ---------------------------------------------------------------------------
# catalog pruning correctness (false-negative-free)
# ---------------------------------------------------------------------------


class TestCatalogPruning:
    def test_filters_never_lose_rows(self, tmp_path):
        rng = np.random.default_rng(5)
        store = make_store(tmp_path, flush_rows=32, n_shards=4)
        all_rows = []
        for k in range(8):
            n = 40
            dev = rng.integers(0, 64, n).astype(np.int32)
            ten = (dev % 3).astype(np.int32)
            et = np.where(rng.random(n) < 0.7, M, A).astype(np.int32)
            cols = make_cols(n, device=dev, tenant=ten, ts0=T0 + k * n)
            cols["event_type"] = et
            cols["mtype_id"] = (dev % 5).astype(np.int32)
            store.append_columns(cols)
            all_rows.extend(zip(dev.tolist(), ten.tolist(), et.tolist(),
                                cols["mtype_id"].tolist(),
                                cols["ts_s"].tolist()))
        store.flush(sync=True)

        def brute(device_id=None, tenant_id=None, event_type=None,
                  mtype_id=None, start_s=None, end_s=None):
            out = []
            for d, t, e, m, ts in all_rows:
                if device_id is not None and d != device_id:
                    continue
                if tenant_id is not None and t != tenant_id:
                    continue
                if event_type is not None and e != event_type:
                    continue
                if mtype_id is not None and m != mtype_id:
                    continue
                if start_s is not None and ts < start_s:
                    continue
                if end_s is not None and ts > end_s:
                    continue
                out.append((d, ts))
            return sorted(out)

        def lane(**filters):
            out = []
            for cols in store.iter_chunks(**filters):
                out.extend(zip(cols["device_id"].tolist(),
                               cols["ts_s"].tolist()))
            return sorted(out)

        cases = [
            {"device_id": 7}, {"device_id": 63}, {"device_id": 1},
            {"tenant_id": 2}, {"event_type": A}, {"mtype_id": 4},
            {"device_id": 9, "event_type": M},
            {"start_s": T0 + 100, "end_s": T0 + 200},
            {"device_id": 3, "start_s": T0 + 50, "end_s": T0 + 290},
            {"device_id": 999},  # absent key: Bloom prunes, zero rows
        ]
        for filters in cases:
            assert lane(**filters) == brute(**filters), filters
        # pruning also holds after compaction rewrites the metadata
        store.compactor.drain()
        for filters in cases:
            assert lane(**filters) == brute(**filters), filters

    def test_absent_device_prunes_without_io(self, tmp_path):
        from sitewhere_tpu.runtime.metrics import MetricsRegistry

        metrics = MetricsRegistry()
        store = make_store(tmp_path, flush_rows=64, n_shards=2,
                           hot_bytes=0, metrics=metrics)
        store.append_columns(make_cols(256, device=np.arange(256) % 8))
        store.flush(sync=True)
        store._cache.loads = 0
        assert scan_rows(store, device_id=100_000) == []
        assert store._cache.loads == 0  # zone-map/Bloom skipped every file


# ---------------------------------------------------------------------------
# retention vs concurrent seal (the ISSUE 13 regression)
# ---------------------------------------------------------------------------


class TestRetentionVsSeal:
    def test_prune_cannot_dangle_a_stalled_seal(self, tmp_path, monkeypatch):
        """A retention pass running while a seal worker is stalled
        MID-WRITE must neither delist nor unlink the in-flight segment:
        pruning goes through the catalog, and an uncommitted job is not
        in the catalog yet."""
        import sitewhere_tpu.store.sealer as sealer_mod

        gate = threading.Event()
        entered = threading.Event()
        stall = {"active": False}
        real_write = sealer_mod.seal_segment_file

        def stalled_write(path, seq, ints, flts, **kw):
            if stall["active"]:
                entered.set()
                assert gate.wait(timeout=10.0)
            return real_write(path, seq, ints, flts, **kw)

        monkeypatch.setattr(sealer_mod, "seal_segment_file",
                            stalled_write)
        store = make_store(tmp_path, flush_rows=16, n_shards=1, workers=1)
        # one OLD committed segment (sealed inline before workers start)
        store.append_columns(make_cols(16, ts0=1000))
        store.flush(sync=False)
        store.sealer.drain()
        assert store.total_events == 16 and len(store._chunks) == 1
        # one NEW buffer worth of OLD-TIMESTAMPED rows, sealed by the
        # (stalled) worker — the adversarial case: its rows are below
        # the cutoff, so a row-level retention would want them gone
        stall["active"] = True
        store.sealer.start()
        try:
            store.append_columns(make_cols(16, ts0=2000))
            store.flush(sync=False)             # close buffer → enqueue
            assert entered.wait(timeout=10.0)   # worker is mid-write
            removed = store.prune_older_than(10_000)
            assert removed == 16  # ONLY the committed segment
            stall["active"] = False
            gate.set()
            store.flush(sync=True)
        finally:
            stall["active"] = False
            gate.set()
            store.sealer.stop()
        # the stalled job committed cleanly after the prune
        assert store.total_events == 16
        assert store.verify_catalog() == []
        rows = scan_rows(store)
        assert len(rows) == 16 and all(t >= 2000 for _, t, _ in rows)
        # the next retention pass collects it normally
        assert store.prune_older_than(10_000) == 16
        assert store.total_events == 0
        assert store.verify_catalog() == []

    def test_prune_goes_through_catalog(self, tmp_path):
        store = make_store(tmp_path, flush_rows=32, n_shards=2)
        store.append_columns(make_cols(64, ts0=1000))
        store.flush(sync=True)   # old rows seal into their own segments
        store.append_columns(make_cols(64, ts0=50_000))
        store.flush(sync=True)
        removed = store.prune_older_than(10_000)
        assert removed == 64
        assert store.total_events == 64
        assert store.verify_catalog() == []
        # restart: the marker kept seqs from regressing
        store2 = make_store(tmp_path)
        assert store2._next_seq >= store._next_seq
        assert store2.total_events == 64


# ---------------------------------------------------------------------------
# compaction: idempotence, crash recovery, id remap
# ---------------------------------------------------------------------------


class TestCompaction:
    def _small_segments(self, store, k=6, rows=8):
        for i in range(k):
            store.append_columns(make_cols(rows, ts0=T0 + i * rows,
                                           device=np.arange(rows) % 4))
            store.flush(sync=False)
        store.sealer.drain()
        store.flush(sync=True)

    def test_compaction_merges_and_is_idempotent(self, tmp_path):
        store = make_store(tmp_path, flush_rows=1024, n_shards=1,
                           compact_min_rows=64)
        self._small_segments(store)
        before = scan_rows(store)
        segs_before = len(store._chunks)
        merged = store.compactor.drain()
        assert merged >= 2
        assert len(store._chunks) < segs_before
        assert scan_rows(store) == before  # content and order unchanged
        # idempotent: nothing left to do
        assert store.compactor.drain() == 0
        assert store.verify_catalog() == []

    def test_event_ids_resolve_through_remap(self, tmp_path):
        store = make_store(tmp_path, flush_rows=1024, n_shards=1,
                           compact_min_rows=64)
        recs = []
        for i in range(4):
            r = store.add_event(device_id=1, tenant_id=0, event_type=M,
                                ts_s=T0 + i, mtype_id=1, value=float(i))
            recs.append(r)
            store.flush(sync=False)
        store.sealer.drain()
        store.flush(sync=True)
        assert store.compactor.drain() >= 2
        for i, rec in enumerate(recs):
            got = store.get_event(rec.event_id)
            assert got.value == float(i)
            # round-trippable: the record carries the REQUESTED id,
            # not the merged segment's internal (seq, row)
            assert got.event_id == rec.event_id
        # and across a restart (provenance re-derives the remap)
        store2 = make_store(tmp_path, flush_rows=1024, n_shards=1)
        for i, rec in enumerate(recs):
            got = store2.get_event(rec.event_id)
            assert got.value == float(i)
            assert got.event_id == rec.event_id

    def test_crashed_swap_resolves_tombstones_at_boot(self, tmp_path):
        """Crash between the merged write and the input unlink: both
        live on disk.  Boot must adopt the merged segment and drop the
        inputs — rows exactly once."""
        store = make_store(tmp_path, flush_rows=1024, n_shards=1,
                           compact_min_rows=64)
        self._small_segments(store, k=3, rows=8)
        before = sorted(scan_rows(store))
        inputs = list(store._chunks)
        merged_cols = {
            name: np.concatenate([c.materialize()[name] for c in inputs])
            for name in COLUMN_NAMES
        }
        seq = store._next_seq
        seg = Segment(seq, merged_cols, shard=inputs[0].shard)
        replaces, base = [], 0
        for c in inputs:
            replaces.append((int(c.seq), base, int(c.n)))
            base += int(c.n)
        seg.replaces = tuple(replaces)
        write_segment_file(store._segment_path(seq), merged_cols, seg)
        # "crash" here: restart on the directory with both generations
        store2 = make_store(tmp_path, flush_rows=1024, n_shards=1)
        assert store2.catalog.tombstones_resolved == len(inputs)
        assert sorted(scan_rows(store2)) == before
        assert store2.verify_catalog() == []
        # old event ids still resolve through recorded provenance
        old_id = event_id(inputs[0].seq, 3)
        assert store2.get_event(old_id).ts_s == T0 + 3

    def test_scan_survives_compaction_mid_scan(self, tmp_path):
        """A scan's snapshot races background compaction: inputs the
        scan has not reached yet get merged and their files unlinked.
        Their rows must be served from the merged segment's recorded
        row range — never silently dropped."""
        store = make_store(tmp_path, flush_rows=1024, n_shards=1,
                           compact_min_rows=64, hot_bytes=0)
        self._small_segments(store, k=4, rows=8)
        expected = scan_rows(store)
        gen = store.iter_chunks()
        first = next(gen)          # snapshot taken, segment 0 served
        got = list(zip(first["device_id"].tolist(),
                       first["ts_s"].tolist(),
                       np.round(first["value"], 5).tolist()))
        assert store.compactor.drain() >= 2   # inputs now unlinked
        for cols in gen:                      # remap serves the rest
            got.extend(zip(cols["device_id"].tolist(),
                           cols["ts_s"].tolist(),
                           np.round(cols["value"], 5).tolist()))
        assert got == expected

    def test_no_merge_across_shard_count_generations(self, tmp_path):
        """Segments sealed under different events.shards values must
        never merge: after a reshard a device can hash to a different
        shard, and a cross-generation merge (order_key = run minimum)
        could move its newer rows ahead of older ones in scan order."""
        store = make_store(tmp_path, flush_rows=1024, n_shards=1,
                           compact_min_rows=64)
        self._small_segments(store, k=2, rows=8)
        # "restart" with a different shard count on the same data dir
        store2 = make_store(tmp_path, flush_rows=1024, n_shards=2,
                            compact_min_rows=64)
        for i in range(2):
            store2.append_columns(make_cols(8, ts0=T0 + 1000 + i * 8,
                                            device=np.arange(8) % 4))
            store2.flush(sync=False)
        store2.sealer.drain()
        store2.flush(sync=True)
        per_device = {}
        for d, t, v in scan_rows(store2):
            per_device.setdefault(d, []).append(t)
        run = store2.compactor._candidates()
        assert run, "small segments should still be mergeable in-gen"
        assert len({(c.shard, c.shard_count) for c in run}) == 1
        store2.compactor.drain()
        after = {}
        for d, t, v in scan_rows(store2):
            after.setdefault(d, []).append(t)
        assert after == per_device  # per-device order survived
        assert store2.verify_catalog() == []

    def test_retention_race_aborts_swap(self, tmp_path):
        """Retention delisting an input mid-merge must abort the swap
        (resurrecting pruned rows would violate the contract)."""
        store = make_store(tmp_path, flush_rows=1024, n_shards=1,
                           compact_min_rows=64)
        self._small_segments(store, k=3, rows=8)
        run = store.compactor._candidates()
        assert len(run) >= 2
        # prune EVERYTHING while the merge would be in flight
        store.prune_older_than(T0 + 10_000)
        assert store.compactor.run_once() == 0
        assert store.total_events == 0
        assert store.verify_catalog() == []


# ---------------------------------------------------------------------------
# tiering: packed hot tier round-trips
# ---------------------------------------------------------------------------


class TestTiering:
    def test_adopt_demote_promote_round_trip(self, tmp_path):
        # tier budget fits ~2 segments of 64 rows (64*80 B each)
        store = make_store(tmp_path, flush_rows=64, n_shards=1,
                           hot_bytes=2 * 64 * 80)
        for k in range(6):
            store.append_columns(make_cols(64, ts0=T0 + 64 * k))
        store.flush(sync=True)
        assert store.hot.demotions > 0  # budget forced evictions
        assert len(store.hot) <= 2
        # the newest segment survived LRU adoption → direct hot hit
        assert store.hot.get(store._chunks[-1].seq) is not None
        before = scan_rows(store)       # UNFILTERED scan: no promotion
        assert store.hot.promotions == 0  # (would thrash the live tier)
        after = scan_rows(store)
        assert after == before          # content bit-identical
        # a WINDOWED query promotes what it materializes...
        old = scan_rows(store, start_s=T0, end_s=T0 + 64 * 2 - 1)
        assert len(old) == 128
        assert store.hot.promotions > 0
        # ...and a repeat of the same window is tier-served
        assert scan_rows(store, start_s=T0, end_s=T0 + 64 * 2 - 1) == old
        assert store.hot.hits > 0

    def test_hot_block_matches_file_contents(self, tmp_path):
        store = make_store(tmp_path, flush_rows=32, n_shards=1)
        store.append_columns(make_cols(32))
        store.flush(sync=True)
        seg = store._chunks[-1]
        pair = store.hot.get(seg.seq)
        assert pair is not None
        hot_cols = unpack_cols(pair[0], pair[1])
        file_cols = seg.materialize()
        for name in COLUMN_NAMES:
            assert np.array_equal(hot_cols[name], file_cols[name]), name

    def test_pack_unpack_round_trip(self):
        cols = make_cols(17)
        cols["received_s"] = np.full(17, 123, np.int32)
        ints, flts = pack_cols(cols)
        back = unpack_cols(ints, flts)
        for name in COLUMN_NAMES:
            assert np.array_equal(back[name], cols[name]), name

    def test_scan_packed_blocks(self, tmp_path):
        from sitewhere_tpu.store.scan import scan_packed

        store = make_store(tmp_path, flush_rows=32, n_shards=2)
        store.append_columns(make_cols(96))
        store.flush(sync=True)
        total = 0
        for ints, flts, seg in scan_packed(store, event_type=M):
            cols = unpack_cols(ints, flts)
            assert (cols["event_type"] == M).all()
            total += ints.shape[1]
        assert total == 96


# ---------------------------------------------------------------------------
# checkpoint section + metrics + misc
# ---------------------------------------------------------------------------


class TestCatalogCheckpoint:
    def test_manifest_snapshot_and_drift(self, tmp_path):
        import json

        store = make_store(tmp_path, flush_rows=32)
        store.append_columns(make_cols(64))
        store.flush(sync=True)
        doc = json.loads(store.catalog.snapshot())
        assert doc["next_seq"] == store._next_seq
        assert {e["seq"] for e in doc["segments"]} == \
            {c.seq for c in store._chunks}
        # an honest manifest restores drift-free
        assert store.catalog.note_restored(doc) == []
        # a manifest naming a segment that never existed reports drift
        stale = dict(doc)
        stale["segments"] = doc["segments"] + [
            {"seq": 9999, "order_key": 9999, "shard": 0, "n": 1,
             "min_ts": 0, "max_ts": 0}]
        drift = store.catalog.note_restored(stale)
        assert any("9999" in d for d in drift)


class TestStoreMetricsAndBench:
    def test_store_metric_family_lints_clean(self, tmp_path):
        from sitewhere_tpu.analysis.metric_names import lint_names
        from sitewhere_tpu.runtime.metrics import MetricsRegistry

        metrics = MetricsRegistry()
        store = make_store(tmp_path, flush_rows=32, metrics=metrics)
        store.append_columns(make_cols(64))
        store.flush(sync=True)
        list(store.iter_chunks(device_id=1))
        store.compactor.run_once()
        names = [n for n in metrics.names() if n.startswith("store.")]
        assert names, "store.* family never registered"
        assert lint_names(names) == []

    def test_store_bench_smoke(self, tmp_path):
        """tools/store_bench.py end-to-end at CI scale: runs, the scan
        lane beats the legacy row scan, and results are bit-identical
        (ISSUE 13 acceptance, scaled)."""
        import importlib.util

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "tools", "store_bench.py")
        spec = importlib.util.spec_from_file_location("store_bench", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        r = mod.run(rows=24_000, batch_rows=2048, flush_rows=2048,
                    keep_dir=str(tmp_path))
        assert r["bit_identical"]
        assert r["retro_matched_rows"] > 0
        assert r["retro_speedup"] > 1.0
        assert r["retro_segments_pruned"] > 0
        assert r["store_seal_segments"] > 0
        assert r["store_append_p99_s"] > 0.0


class TestSealFailClosed:
    def test_sync_flush_raises_while_seal_fails_then_heals(self, tmp_path):
        from sitewhere_tpu.runtime import faults

        store = make_store(tmp_path, flush_rows=16, n_shards=1, workers=1,
                           max_seal_retries=1000)
        store.sealer.start()
        try:
            faults.inject("event_store.seal", exc=OSError("disk full"),
                          times=None)
            store.append_columns(make_cols(32))
            with pytest.raises(OSError):
                store.flush(sync=True)
            # fail-closed: rows still readable (parked, not dropped)
            assert store.total_events == 32
            faults.clear("event_store.seal")
            store.flush(sync=True)   # retry_parked + drain heals
            assert store.total_events == 32
            assert store.verify_catalog() == []
        finally:
            faults.clear()
            store.sealer.stop()

    def test_inline_pump_parks_job_on_non_oserror(self, tmp_path):
        """The drain fallback (no live workers) must park — never drop
        — a job that dies on a NON-OSError: a lost job would let the
        next sync flush commit a journal offset over rows that exist
        nowhere."""
        from sitewhere_tpu.runtime import faults

        store = make_store(tmp_path, flush_rows=16, n_shards=1)
        try:
            faults.inject("event_store.seal")  # FaultInjected, once
            store.append_columns(make_cols(32))
            with pytest.raises(Exception):
                store.flush(sync=False)        # inline pump raises
            assert store.sealer.parked_count() >= 1  # parked, not lost
            assert store.total_events == 32    # rows still visible
            store.flush(sync=True)             # retry heals (fault spent)
            assert store.total_events == 32
            assert store.sealer.parked_count() == 0
            assert store.verify_catalog() == []
        finally:
            faults.clear()

    def test_writer_valve_bounds_seal_backlog(self, tmp_path):
        """With no workers draining, the append-side valve seals
        inline once the queue falls behind — the legacy 4×-flush_rows
        memory bound, pool edition."""
        store = make_store(tmp_path, flush_rows=64, n_shards=1, workers=1)
        # sealer never started: queue only drains through the valve
        for k in range(20):
            store.append_columns(make_cols(64, ts0=T0 + 64 * k))
        bound = 4 + store.sealer.n_workers + 1
        assert store.sealer.queue_depth() <= bound
        assert store.sealer.sealed_segments > 0  # valve did real seals
        store.flush(sync=True)
        assert store.total_events == 20 * 64

    def test_terminal_failure_dead_letters_not_wedges(self, tmp_path):
        from sitewhere_tpu.runtime import faults

        store = make_store(tmp_path, flush_rows=16, n_shards=1, workers=1,
                           max_seal_retries=0, seal_retry_window_s=0.0)
        store.sealer.start()
        try:
            faults.inject("event_store.seal", exc=OSError("disk dead"),
                          times=None)
            store.append_columns(make_cols(32))
            store.flush(sync=True)   # dead-letter IS the durable trace
            assert store.sealed_dead_lettered == 32
            assert store.total_events == 0
            faults.clear("event_store.seal")
            store.append_columns(make_cols(8, ts0=T0 + 100))
            store.flush(sync=True)   # the store is not wedged
            assert store.total_events == 8
        finally:
            faults.clear()
            store.sealer.stop()


class TestEgressColumnsView:
    def test_lazy_enrichment_fetch(self):
        from sitewhere_tpu.runtime.dispatcher import EgressColumns

        host = {name: np.arange(4, dtype=np.int32)
                for name in EgressColumns.HOST_COLUMNS}
        fetches = []

        class Out:
            def __getattr__(self, name):
                fetches.append(name)
                return np.full(4, 7, np.int32)

        cols = EgressColumns(host, Out())
        assert not fetches                      # nothing eager
        assert cols["device_id"] is host["device_id"]
        assert not fetches                      # host access is free
        assert (cols["area_id"] == 7).all()
        # first enrichment touch fetches ALL five once (thread-safe
        # memo), then releases the step output
        assert sorted(fetches) == sorted(EgressColumns.ENRICHMENT_COLUMNS)
        assert cols._out is None                # device buffers released
        assert (cols["area_id"] == 7).all()
        assert len(fetches) == 5                # memoized, no refetch
        assert "payload_ref" in cols and "asset_id" in cols
        assert "nope" not in cols
        assert len(dict(cols.items())) == len(cols) == 19

    def test_append_columns_accepts_view(self, tmp_path):
        from sitewhere_tpu.runtime.dispatcher import EgressColumns

        n = 24
        host = {name: np.arange(n, dtype=np.int32)
                if name not in ("value", "lat", "lon", "elevation")
                else np.zeros(n, np.float32)
                for name in EgressColumns.HOST_COLUMNS}
        host["ts_s"] = np.arange(T0, T0 + n, dtype=np.int32)

        class Out:
            def __getattr__(self, name):
                return np.zeros(n, np.int32)

        store = make_store(tmp_path, flush_rows=16)
        added = store.append_columns(EgressColumns(host, Out()),
                                     mask=np.arange(n) % 2 == 0)
        assert added == n // 2
        store.flush(sync=True)
        assert store.total_events == n // 2

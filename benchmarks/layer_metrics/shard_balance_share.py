"""Batcher to ring: rows emitted to the emptiest shard over rows emitted
to the fullest, in percent, window deltas of the sharded batcher's
``ingest.shard_rows_emitted.<s>``.  A plan goes out when its fullest
segment fills, so anything under 100 is width the other chips stepped
empty.  A program without the counters (one chip) reads nothing."""


def read(run):
    rows = [run.counter(f"ingest.shard_rows_emitted.{s}")
            for s in range(run.n_shards)]
    if not max(rows):
        return None
    return 100.0 * min(rows) / max(rows)

"""Full-width column batches from waiting clients (closed loop).

Edge aggregators and bulk importers hand over decoded columns: each of
``clients`` threads puts one pre-resolved batch of ``pipeline.width``
rows into ``dispatcher.ingest_arrays`` and sends its next when the
connector has delivered every row of the last, or after
``reply_timeout_s`` without that (those rows are failed).  No drain and
no wait for NORMAL inside the window.

A body is half measurements, half locations, devices in shard-block
order (``width / n_shards`` rows of each shard's devices, each device
at most once) so that every send is one full-width fill plan on any
mesh; ``ts_ns`` is a permutation, so no two rows of a send tie.  The
stamp is ``ts_s``: an hour back plus the send's sequence number.
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np


class Traffic:
    def __init__(self, params: dict, dep, rng) -> None:
        self.dep = dep
        self.clients = int(params["clients"])
        self.reply_timeout_s = float(params["reply_timeout_s"])
        self.prime_sends = int(params["prime_sends"])
        self.base_s = int(time.time()) - 3600
        width, n_shards = dep.width, dep.n_shards
        seg = width // n_shards
        per_shard = len(dep.handles) // n_shards
        if seg > per_shard:
            raise ValueError("a batch names each device at most once: "
                             f"{seg} rows a shard > {per_shard} devices")
        by_shard = [dep.handles[s * per_shard:(s + 1) * per_shard]
                    for s in range(n_shards)]
        (v0, v1), (a0, a1), (o0, o1) = (params["value_range"],
                                        params["lat_range"],
                                        params["lon_range"])
        self.bodies = []
        for _ in range(int(params["pool_batches"])):
            self.bodies.append({
                "dev": np.concatenate([
                    rng.permutation(by_shard[s])[:seg]
                    for s in range(n_shards)]).astype(np.int32),
                "etype": (rng.random(width) < 0.5).astype(np.int32),
                "value": rng.uniform(v0, v1, width).astype(np.float32),
                "lat": rng.uniform(a0, a1, width).astype(np.float32),
                "lon": rng.uniform(o0, o1, width).astype(np.float32),
                "ts_ns": rng.permutation(width).astype(np.int64) * 1000})
        self.mtype = np.full(width, dep.mtype, np.int32)
        self.order = rng.permutation(len(self.bodies))
        self.width = width

    def max_sends(self, seconds: float) -> int:
        # no client can turn a batch around faster than this
        return self.prime_sends + self.clients * (int(seconds / 0.005) + 2)

    def ts_s_of(self, seq: int) -> int:
        return self.base_s + seq

    def seq_of(self, ts_s, ts_ns):
        return ts_s.astype(np.int64) - self.base_s

    def _send(self, client, seq: int, measured: bool) -> None:
        bi = int(self.order[seq % len(self.order)])
        b = self.bodies[bi]
        cols = dict(device_id=b["dev"], event_type=b["etype"],
                    ts_s=np.full(self.width, self.base_s + seq, np.int32),
                    ts_ns=b["ts_ns"].astype(np.int32), mtype_id=self.mtype,
                    value=b["value"], lat=b["lat"], lon=b["lon"])
        ingest = self.dep.d.ingest_arrays
        client.send(seq, bi, self.width, time.perf_counter(),
                    lambda: ingest(**cols), measured)

    def prime(self, client) -> None:
        for seq in range(self.prime_sends):
            self._send(client, seq, False)

    def run(self, client, t_begin: float, seconds: float) -> None:
        t_end = t_begin + seconds
        seqs = itertools.count(self.prime_sends)

        def one_client() -> None:
            wait = t_begin - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            while time.perf_counter() < t_end:
                seq = next(seqs)
                self._send(client, seq, True)
                # once the window is over nobody waits: the final drain
                # delivers what is in flight
                client.delivery.wait(
                    seq, self.width,
                    min(self.reply_timeout_s, t_end - time.perf_counter()))

        threads = [threading.Thread(target=one_client,
                                    name=f"bench-client-{j}")
                   for j in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()


def build(params: dict, dep, rng) -> Traffic:
    return Traffic(params, dep, rng)

"""Full-width column batches of one tenant each, from waiting clients
(closed loop), with the claimed tenant's id on every row.

As ``columns-closed-loop``, for a deployment of kind
``tenants-presence``: a body is ``pipeline.width`` measurements of one
tenant's devices, each device at most once, handed to
``dispatcher.ingest_arrays`` with a ``tenant_id`` column.  One send in
``wrong_tenant_every`` claims the next tenant for its devices: the
system has to refuse it, nothing of it is delivered, so it is logged
unmeasured (a probe of the guarantee, not load) and its client does not
wait for it.

Each tenant's silent devices get one event, stamped
``presence.silent_age_s`` back: the first tenant's in the priming pass,
every other tenant's as the window's first sends.  ``prime`` waits
until the sweep has reported the first cohort, so that what a report
compiles is compiled before the window; the cohorts are equally long,
so the later reports run the same programs.

The stamp is ``ts_ns``: the send's sequence number times 1000 (one
stamp a send and each device at most once in a body, so a device's
newest event is never a tie); ``ts_s`` is an hour back plus the
sequence number, or the cohort's age.
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
        self.every = int(params["wrong_tenant_every"])
        self.flag_wait_s = float(params["flag_wait_s"])
        self.t_first_s = int(time.time())
        self.base_s = self.t_first_s - 3600
        self.age_s = int(dep.config["presence"]["silent_age_s"])
        width = self.width = dep.width
        tenants = list(dep.tenant_ids.values())
        lo, hi = params["value_range"]

        def body(dev, claimed):
            return {"dev": dev.astype(np.int32),
                    "tenant": np.full(len(dev), claimed, np.int32),
                    "value": rng.uniform(lo, hi, len(dev)).astype(np.float32)}

        active = {t: dep.handles[(dep.owner == t) & ~dep.silent]
                  for t in tenants}
        for t, handles in active.items():
            if width > len(handles):
                raise ValueError("a batch names each device at most once: "
                                 f"{width} rows > {len(handles)} devices")
        self.bodies = [body(rng.permutation(active[t])[:width], t)
                       for _, t in zip(range(int(params["pool_batches"])),
                                       itertools.cycle(tenants))]
        self.order = rng.permutation(len(self.bodies))
        # devices of one tenant under the next one's id
        self.wrong = list(range(len(self.bodies),
                                len(self.bodies) + len(tenants)))
        self.bodies += [body(rng.permutation(active[t])[:width],
                             tenants[(i + 1) % len(tenants)])
                        for i, t in enumerate(tenants)]
        self.cohorts = list(range(len(self.bodies),
                                  len(self.bodies) + len(tenants)))
        self.bodies += [body(dep.handles[(dep.owner == t) & dep.silent], t)
                        for t in tenants]
        if len({len(self.bodies[c]["dev"]) for c in self.cohorts}) != 1:
            raise ValueError("the tenants' silent cohorts differ in length")
        self.aged: set = set()        # sends stamped silent_age_s back
        self.mtype = int(dep.mtype)

    def max_sends(self, seconds: float) -> int:
        return (self.prime_sends + len(self.cohorts)
                + self.clients * (int(seconds / 0.005) + 2))

    def ts_s_of(self, seq: int) -> int:
        if seq in self.aged:
            return self.t_first_s - self.age_s
        return self.base_s + seq

    def seq_of(self, ts_s, ts_ns):
        return ts_ns.astype(np.int64) // 1000

    def _send(self, client, seq: int, bi: int, measured: bool) -> None:
        b = self.bodies[bi]
        n = len(b["dev"])
        if bi in self.cohorts:
            self.aged.add(seq)
        cols = dict(device_id=b["dev"], tenant_id=b["tenant"],
                    event_type=np.zeros(n, np.int32),
                    ts_s=np.full(n, self.ts_s_of(seq), np.int32),
                    ts_ns=np.full(n, seq * 1000, np.int32),
                    mtype_id=np.full(n, self.mtype, np.int32),
                    value=b["value"])
        ingest = self.dep.d.ingest_arrays
        client.send(seq, bi, n, time.perf_counter(),
                    lambda: ingest(**cols), measured)

    def _pool(self, seq: int) -> tuple:
        """(body index, whether the system has to take it) of send
        ``seq`` from the pool."""
        if seq % self.every == self.every - 1:
            return self.wrong[(seq // self.every) % len(self.wrong)], False
        return int(self.order[seq % len(self.order)]), True

    def prime(self, client) -> None:
        self._send(client, 0, self.cohorts[0], False)
        self._send(client, 1, self.wrong[0], False)
        for seq in range(2, self.prime_sends):
            self._send(client, seq, int(self.order[seq % len(self.order)]),
                       False)
        want = len(self.bodies[self.cohorts[0]]["dev"])
        presence = self.dep.inst.presence
        deadline = time.monotonic() + self.flag_wait_s
        while (presence.total_marked_missing < want
               and time.monotonic() < deadline):
            time.sleep(0.05)

    def run(self, client, t_begin: float, seconds: float) -> None:
        t_end = t_begin + seconds
        first = self.prime_sends
        seqs = itertools.count(first + len(self.cohorts) - 1)

        def one_client(j: int) -> None:
            wait = t_begin - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if j == 0:
                for k, cohort in enumerate(self.cohorts[1:]):
                    self._send(client, first + k, cohort, True)
            while time.perf_counter() < t_end:
                seq = next(seqs)
                bi, taken = self._pool(seq)
                self._send(client, seq, bi, taken)
                if taken:
                    # once the window is over nobody waits: the final
                    # drain delivers what is in flight
                    client.delivery.wait(
                        seq, self.width,
                        min(self.reply_timeout_s,
                            t_end - time.perf_counter()))

        threads = [threading.Thread(target=one_client, args=(j,),
                                    name=f"bench-client-{j}")
                   for j in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()


def build(params: dict, dep, rng) -> Traffic:
    return Traffic(params, dep, rng)

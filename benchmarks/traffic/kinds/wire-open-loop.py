"""NDJSON payloads on a fixed schedule (open loop).

Many gateways each forward a batch of sensor readings: payloads of
``lines_per_payload`` Measurement lines go into
``dispatcher.ingest_wire_lines`` at ``rate_events_per_s``, evenly
spaced, whether or not the system keeps up.  Payload *i* is due at
``t_begin + i * interval`` and belongs to sender ``i % senders`` (each
its own ``source_id``), so one blocked call does not hold the schedule;
a sender that falls behind sends at once and its lateness is logged.

Bodies come from a seeded pool and are stamped per send: ``eventDate``
is epoch milliseconds, an hour back plus the send's sequence number
(the finest stamp the decoder keeps exactly enough: it divides by
1000, so the sequence comes back as seconds * 1000 + ns / 1e6).  One
stamp per send and each device at most once in a body: a device's
newest event is never a tie.
"""

from __future__ import annotations

import threading
import time

import numpy as np

STAMP = b"@eventDate@ms"          # 13 bytes, as wide as epoch millis


class Traffic:
    def __init__(self, params: dict, dep, rng) -> None:
        self.dep = dep
        self.lines = int(params["lines_per_payload"])
        self.rate = float(params["rate_events_per_s"])
        self.senders = int(params["senders"])
        self.prime_sends = int(params["prime_sends"])
        self.interval = self.lines / self.rate
        self.base_s = int(time.time()) - 3600
        lo, hi = params["value_range"]
        name = dep.config["measurement"]
        tokens, handles = dep.tokens, dep.handles
        if self.lines > len(tokens):
            raise ValueError("a payload names each device at most once: "
                             f"{self.lines} lines > {len(tokens)} devices")
        self.bodies, self.payloads = [], []
        zeros = np.zeros(self.lines, np.float32)
        for _ in range(int(params["pool_payloads"])):
            pick = rng.permutation(len(tokens))[:self.lines]
            vals = np.round(rng.uniform(lo, hi, self.lines), 3)
            self.payloads.append("\n".join(
                f'{{"deviceToken":"{tokens[i]}","type":"Measurement",'
                f'"request":{{"name":"{name}","value":{v!r},'
                f'"eventDate":{STAMP.decode()}}}}}'
                for i, v in zip(pick.tolist(), vals.tolist())).encode())
            self.bodies.append({
                "dev": handles[pick], "etype": np.zeros(self.lines, np.int32),
                "value": vals.astype(np.float32), "lat": zeros, "lon": zeros,
                "ts_ns": np.zeros(self.lines, np.int64)})
        # the order the pool is sent in is the seed's too
        self.order = rng.permutation(len(self.bodies))

    def max_sends(self, seconds: float) -> int:
        return self.prime_sends + int(seconds / self.interval) + 2

    def ts_s_of(self, seq: int) -> int:
        return self.base_s + seq // 1000

    def seq_of(self, ts_s, ts_ns):
        return ((ts_s.astype(np.int64) - self.base_s) * 1000
                + np.rint(ts_ns / 1e6).astype(np.int64))

    def _send(self, client, seq: int, due: float, source: str,
              measured: bool) -> None:
        body = int(self.order[seq % len(self.order)])
        stamp = b"%013d" % (self.base_s * 1000 + seq)
        payload = self.payloads[body].replace(STAMP, stamp)
        ingest = self.dep.d.ingest_wire_lines
        client.send(seq, body, self.lines, due,
                    lambda: ingest(payload, source_id=source), measured)

    def prime(self, client) -> None:
        for seq in range(self.prime_sends):
            self._send(client, seq, time.perf_counter(), "gw-prime", False)

    def run(self, client, t_begin: float, seconds: float) -> None:
        n = int(np.ceil(seconds / self.interval - 1e-9))
        first = self.prime_sends
        t_end = t_begin + seconds
        for i in range(n):           # due inside the window: attempted
            client.sends.planned(first + i, 0, self.lines,
                                 t_begin + i * self.interval, True)

        def sender(j: int) -> None:
            for i in range(j, n, self.senders):
                due = t_begin + i * self.interval
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                elif time.perf_counter() >= t_end:
                    return           # the window is over: left unsent
                self._send(client, first + i, due, f"gw-{j}", True)

        threads = [threading.Thread(target=sender, args=(j,),
                                    name=f"bench-gw-{j}")
                   for j in range(self.senders)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wait = t_end - time.perf_counter()
        if wait > 0:
            time.sleep(wait)


def build(params: dict, dep, rng) -> Traffic:
    return Traffic(params, dep, rng)

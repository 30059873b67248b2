"""NDJSON payloads of one tenant each on a fixed schedule (open loop),
for a deployment of kind ``tenant-topics-presence``.

A hosting operator runs several customers' fleets on one instance: each
customer's gateways publish batches to that tenant's topic.  As
``wire-open-loop``: payloads of ``lines_per_payload`` Measurement lines
go into ``dispatcher.ingest_wire_lines`` at ``rate_events_per_s``,
evenly spaced, payload *i* due at ``t_begin + i * interval`` and sent by
sender ``i % senders``.  What differs:

- every payload is ONE tenant's: its devices are drawn without repeat
  from that tenant's live fleet (all of them where the tenant has fewer
  than a payload's lines) and the send names the tenant,
  ``ingest_wire_lines(payload, source_id=..., tenant=...)``.  The tenant
  of a pool payload is drawn from the seed with probability in
  proportion to the tenant's live devices;
- after every ``probe_every``-th measured send the same sender makes one
  extra, UNMEASURED send whose devices belong to another tenant than the
  one it names: the system has to refuse every row, nothing of it is
  delivered, the reference accounts for it (a probe of the guarantee,
  not load);
- the silent cohorts (``dep.cohort``) get their one event in the priming
  pass, a payload a tenant: cohort 0 stamped well over
  ``missing_after_s`` back, and ``prime`` waits until the sweep has
  reported it; then cohorts 1.. stamped so that they cross
  ``missing_after_s`` one after another, a scan interval apart (whole
  seconds, at least one), the first about half an interval after the
  priming pass ends.  Measured sends keep ``wire-open-loop``'s stamps (an
  hour back plus the sequence number), so no live device goes silent.
  When the window is over ``run`` waits for a sweep that judged at or
  after the last crossing (``presence.last_sweep_s``) and leaves that
  second in ``swept_s`` for the reference.

The stamp is ``eventDate`` in epoch milliseconds.  A send of the pool:
an hour back plus the sequence number, as ``wire-open-loop``.  A cohort's
send: its aged second, with the sequence number (under 1000 in the
priming pass) as the milliseconds.  ``replay_record`` is (journal offset,
body) of one unmeasured priming send of a tenant, for the kind's replay
comparison.
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
        self.probe_every = int(params["probe_every"])
        self.flag_wait_s = float(params["flag_wait_s"])
        self.interval = self.lines / self.rate
        self.t_first_s = int(time.time())
        self.base_s = self.t_first_s - 3600
        presence = dep.config["config"]["presence"]
        self.missing_after_s = int(presence["missing_after_s"])
        self.scan_s = float(presence["scan_interval_s"])
        self.swept_s = self.cross_last_s = self.t_first_s
        self.aged: dict = {}          # seq -> the aged second it carries
        lo, hi = params["value_range"]
        name = dep.config["measurement"]
        tokens = dep.tokens
        names = list(dep.tenant_ids)
        ids = [dep.tenant_ids[t] for t in names]
        self.bodies, self.payloads, self.named = [], [], []

        def body(pick, claimed: int) -> int:
            vals = np.round(rng.uniform(lo, hi, len(pick)), 3)
            self.payloads.append("\n".join(
                f'{{"deviceToken":"{tokens[i]}","type":"Measurement",'
                f'"request":{{"name":"{name}","value":{v!r},'
                f'"eventDate":{STAMP.decode()}}}}}'
                for i, v in zip(pick.tolist(), vals.tolist())).encode())
            self.bodies.append({
                "dev": dep.handles[pick],
                "tenant": np.full(len(pick), ids[claimed], np.int32),
                "value": vals.astype(np.float32)})
            self.named.append(names[claimed])
            return len(self.bodies) - 1

        live = [np.nonzero((dep.owner == t) & (dep.cohort < 0))[0]
                for t in ids]
        share = np.asarray([len(x) for x in live], np.float64)
        drawn = rng.choice(len(ids), int(params["pool_payloads"]),
                           p=share / share.sum())
        pool = [body(rng.permutation(live[t])[:self.lines], t)
                for t in drawn.tolist()]
        # the order the pool is sent in is the seed's too
        self.order = rng.permutation(pool)
        # devices of one tenant under the next one's name
        self.wrong = [body(rng.permutation(live[t])[:self.lines],
                           (t + 1) % len(ids)) for t in range(len(ids))]
        # a cohort is a payload a tenant: that tenant's devices of it
        self.cohorts = []
        for k in range(int(dep.cohort.max()) + 1):
            mine = [np.nonzero((dep.owner == t) & (dep.cohort == k))[0]
                    for t in ids]
            self.cohorts.append([body(pick, t) for t, pick in enumerate(mine)
                                 if len(pick)])
        self.cohort_devices = [sum(len(self.bodies[b]["dev"]) for b in c)
                               for c in self.cohorts]
        # sequence numbers: the priming pass, then the measured sends,
        # then the probes made between them
        self.first = (sum(len(c) for c in self.cohorts) + self.prime_sends
                      + 1)
        if self.first >= 1000:
            raise ValueError(f"{self.first} priming sends: a cohort's "
                             f"stamp carries the sequence number as "
                             f"milliseconds")

    def _measured(self, seconds: float) -> int:
        return int(np.ceil(seconds / self.interval - 1e-9))

    def max_sends(self, seconds: float) -> int:
        n = int(seconds / self.interval) + 2
        return self.first + n + n // self.probe_every + 2

    def ts_s_of(self, seq: int) -> int:
        if seq in self.aged:
            return self.aged[seq]
        return self.base_s + seq // 1000

    def seq_of(self, ts_s, ts_ns):
        ms = np.rint(ts_ns / 1e6).astype(np.int64)
        s = ts_s.astype(np.int64) - self.base_s
        return np.where(s < 0, ms, s * 1000 + ms)

    def _send(self, client, seq: int, body: int, due: float, source: str,
              measured: bool, aged_s=None) -> None:
        if aged_s is None:
            stamp = b"%013d" % (self.base_s * 1000 + seq)
        else:
            self.aged[seq] = int(aged_s)
            stamp = b"%013d" % (int(aged_s) * 1000 + seq)
        payload = self.payloads[body].replace(STAMP, stamp)
        ingest, tenant = self.dep.d.ingest_wire_lines, self.named[body]
        client.send(seq, body, len(self.bodies[body]["dev"]), due,
                    lambda: ingest(payload, source_id=source, tenant=tenant),
                    measured)

    def _pool_body(self, seq: int) -> int:
        return int(self.order[seq % len(self.order)])

    def prime(self, client) -> None:
        """The main thread's: a program whose wire intake takes no
        tenant fails here, at the first send, and the run with it."""
        inst = self.dep.inst
        seq = 0
        for b in self.cohorts[0]:
            self._send(client, seq, b, time.perf_counter(), "gw-prime",
                       False, aged_s=self.t_first_s - self.missing_after_s
                       - 600)
            seq += 1
        for _ in range(self.prime_sends):
            offset = int(inst.ingest_journal.end_offset)
            self._send(client, seq, self._pool_body(seq),
                       time.perf_counter(), "gw-prime", False)
            self.replay_record = (offset, self._pool_body(seq))
            seq += 1
        self._send(client, seq, self.wrong[0], time.perf_counter(),
                   "gw-prime", False)
        seq += 1
        # what the first report runs is run before the window
        deadline = time.monotonic() + self.flag_wait_s
        while (inst.presence.total_marked_missing < self.cohort_devices[0]
               and time.monotonic() < deadline):
            time.sleep(0.05)
        apart = max(1, round(self.scan_s))
        cross = int(time.time()) + 1 + max(1, round(self.scan_s / 2))
        for cohort in self.cohorts[1:]:
            for b in cohort:
                self._send(client, seq, b, time.perf_counter(), "gw-prime",
                           False, aged_s=cross - self.missing_after_s - 1)
                seq += 1
            self.cross_last_s = cross
            cross += apart
        if seq != self.first:
            raise RuntimeError(f"{seq} priming sends, {self.first} planned")

    def run(self, client, t_begin: float, seconds: float) -> None:
        n = self._measured(seconds)
        first = self.first
        t_end = t_begin + seconds
        for i in range(n):           # due inside the window: attempted
            seq = first + i
            client.sends.planned(
                seq, 0, len(self.bodies[self._pool_body(seq)]["dev"]),
                t_begin + i * self.interval, True)

        def sender(j: int) -> None:
            for i in range(j, n, self.senders):
                due = t_begin + i * self.interval
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                elif time.perf_counter() >= t_end:
                    return           # the window is over: left unsent
                seq = first + i
                self._send(client, seq, self._pool_body(seq), due,
                           f"gw-{j}", True)
                if (i + 1) % self.probe_every == 0:
                    k = (i + 1) // self.probe_every - 1
                    self._send(client, first + n + k,
                               self.wrong[k % len(self.wrong)],
                               time.perf_counter(), f"gw-{j}", False)

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
        # the drain's part: a sweep that judged at or after the last
        # crossing has ended, so every cohort is reported
        presence = self.dep.inst.presence
        give_up = self.cross_last_s + 2 * self.scan_s + 5.0
        while ((presence.last_sweep_s or 0) < self.cross_last_s
               and time.time() < give_up):
            time.sleep(0.05)
        self.swept_s = int(presence.last_sweep_s or self.t_first_s)


def build(params: dict, dep, rng) -> Traffic:
    return Traffic(params, dep, rng)

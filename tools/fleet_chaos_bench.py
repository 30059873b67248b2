#!/usr/bin/env python
"""Fleet chaos bench: prove goodput degrades smoothly, not collapses.

A 3-host in-process fleet (real :class:`~sitewhere_tpu.instance.Instance`
objects wired over localhost RPC — the same topology the multi-host
tests use) takes sustained keyed traffic at host 0's frontend while
host 2 is driven through the ISSUE-14 failure script:

1. **baseline** — all three hosts healthy; record per-host goodput.
2. **shed** — host 2 forced into SHEDDING: its admission refuses
   telemetry, host 0's health table must learn it (heartbeat +
   response piggyback), park the spool, and pace single probe batches;
   the device-facing edge refuses pure host-2 payloads with host 2's
   Retry-After hint.
3. **partition** — host 2's endpoint additionally drops every packet
   (``faults.net_inject``): the failure detector walks SUSPECT → DOWN;
   probes stay paced.
4. **recover** — partition healed, overload cleared: the health table
   returns to ALIVE/NORMAL and the spool drains to zero.

Asserted contract (the bench FAILS otherwise):

- healthy-host goodput never collapses (min phase ≥ ``collapse_frac``
  of baseline);
- send attempts to the unhealthy peer stay BOUNDED (paced probes, not
  a retry storm);
- ZERO forward-plane dead letters — every retained row is replayable
  and the spool drains to zero on recovery;
- the health table does not flap (bounded transitions for host 2).

Usage::

    python tools/fleet_chaos_bench.py [--smoke] [--json FLEETCHAOS.json]

``--smoke`` shrinks phases for the tier-1 gate; the full run writes the
FLEETCHAOS_rNN.json evidence captures.
"""

import argparse
import dataclasses
import json
import math
import os
import shutil
import socket
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# deterministic CPU: the bench measures host-plane behavior, not chips
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from sitewhere_tpu.runtime import faults  # noqa: E402

N_HOSTS = 3
N_DEVICES = 48
T0 = 1_754_000_000


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _config(data_dir, ports, pid, heartbeat_s):
    from sitewhere_tpu.runtime.config import Config
    from sitewhere_tpu.runtime.overload import Watermarks

    return Config({
        "instance": {"id": f"fleet-{pid}", "data_dir": data_dir},
        "pipeline": {"width": 64, "registry_capacity": 256,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 86400},
        "checkpoint": {"interval_s": 0},
        "analytics": {"enabled": False},
        "slo": {"enabled": False},
        # only the script moves the ladder: a forced state must hold for
        # its phase (a short cooldown would let the controller
        # self-recover mid-test), and a loaded machine's own pressure
        # signals must not escalate a host past the state it was given
        "overload": {"enabled": True, "cooldown_s": 600.0, "watermarks": {
            f.name: [1e9, 2e9, 3e9] for f in dataclasses.fields(Watermarks)}},
        "rpc": {
            "server": {"enabled": True, "host": "127.0.0.1",
                       "port": ports[pid]},
            "process_id": pid,
            "peers": [f"127.0.0.1:{p}" for p in ports],
            "forward_deadline_ms": 10.0,
            "heartbeat_interval_s": heartbeat_s,
            "call_timeout_s": 3.0,
        },
        "security": {"jwt_secret": "fleet-chaos-secret"},
    }, apply_env=False)


def _boot_fleet(root, heartbeat_s):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.rpc.forward import owning_process

    ports = [_free_port() for _ in range(N_HOSTS)]
    insts = []
    for pid in range(N_HOSTS):
        inst = Instance(_config(os.path.join(root, f"host{pid}"), ports,
                                pid, heartbeat_s))
        inst.start()
        insts.append(inst)
    # every host registers the devices IT owns (dense handles are
    # host-local; forwarded rows must find a registered device)
    tokens_by_owner = {p: [] for p in range(N_HOSTS)}
    for i in range(N_DEVICES):
        tok = f"d-{i}"
        tokens_by_owner[owning_process(tok, N_HOSTS)].append(tok)
    for pid, inst in enumerate(insts):
        dm = inst.device_management
        dm.create_device_type(token="sensor", name="Sensor")
        for tok in tokens_by_owner[pid]:
            dm.create_device(token=tok, device_type="sensor")
            dm.create_device_assignment(device=tok)
    return insts, ports, tokens_by_owner


def _payload(tokens, seq):
    lines = []
    for k, tok in enumerate(tokens):
        lines.append(json.dumps({
            "deviceToken": tok, "type": "Measurement",
            "request": {"name": "temp", "value": float(seq % 50),
                        "eventDate": T0 + seq * 64 + k},
        }))
    return "\n".join(lines).encode()


class _Driver(threading.Thread):
    """Sustained mixed traffic into host 0's frontend: every round one
    payload carrying rows for ALL owners (the gateway-bulk shape — the
    edge gate never refuses it, the spool absorbs unhealthy owners)."""

    def __init__(self, fwd, tokens_by_owner, period_s=0.02):
        super().__init__(name="fleet-driver", daemon=True)
        self.fwd = fwd
        self.tokens_by_owner = tokens_by_owner
        self.period_s = period_s
        self.sent_rows = {p: 0 for p in tokens_by_owner}
        self.seq = 0
        self._halt = threading.Event()
        self._lock = threading.Lock()

    def run(self):
        while not self._halt.wait(self.period_s):
            batch = {p: toks[self.seq % len(toks):][:4]
                     for p, toks in self.tokens_by_owner.items()}
            payload = b"\n".join(
                _payload(toks, self.seq) for toks in batch.values() if toks)
            self.seq += 1
            self.fwd.ingest_payload(payload, source_id="fleet-bench")
            with self._lock:
                for p, toks in batch.items():
                    self.sent_rows[p] += len(toks)

    def snapshot(self):
        with self._lock:
            return dict(self.sent_rows)

    def stop(self):
        self._halt.set()
        self.join(timeout=10)


def _until(cond, timeout_s, poke=lambda: None):
    """Poll ``cond`` until it holds, calling ``poke`` between polls;
    its last value once ``timeout_s`` has passed.  (``tests/time_limit``
    has the tests' copy, without ``poke``: it imports pytest, which a
    tool run from the command line does not need.)"""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        poke()
        time.sleep(0.1)
    return cond()


def _accepted(insts):
    return [int(i.dispatcher.metrics_snapshot()["accepted"]) for i in insts]


def _count_ingest_calls(demux):
    """Wrap one peer demux's call() to count events.ingest attempts —
    the bounded-probe assertion reads this."""
    counts = {"events.ingest": 0}
    orig = demux.call

    def counted(method, *a, **kw):
        if method in counts:
            counts[method] += 1
        return orig(method, *a, **kw)

    demux.call = counted
    return counts


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--json", dest="json_out")
    args = parser.parse_args(argv)

    phase_s = 1.5 if args.smoke else 4.0
    heartbeat_s = 0.1
    probe_interval_s = 2 * heartbeat_s      # health-table default
    drain_timeout_s = 30.0
    collapse_frac = 0.25                    # generous: CI boxes jitter

    from sitewhere_tpu.runtime.overload import OverloadShed, OverloadState

    root = tempfile.mkdtemp(prefix="fleet-chaos-")
    failures = []
    report = {"phases": {}, "smoke": bool(args.smoke)}
    insts = []
    driver = None
    try:
        t_boot = time.perf_counter()
        insts, ports, tokens_by_owner = _boot_fleet(root, heartbeat_s)
        report["boot_s"] = round(time.perf_counter() - t_boot, 2)
        fwd = insts[0].forwarder
        sick = 2                            # the host under test
        sick_ep = f"127.0.0.1:{ports[sick]}"

        # warm-up OUTSIDE the timed phases: the first batch on every
        # host pays the jit compile of the pipeline step — baseline
        # goodput must measure steady state, not compile time
        for p, toks in tokens_by_owner.items():
            fwd.ingest_payload(_payload(toks[:4], 0), source_id="warmup")
        fwd.flush(wait=True)
        if not _until(lambda: all(a >= 4 for a in _accepted(insts)), 120,
                      poke=fwd.flush):
            failures.append("warm-up rows never landed on every host")
        report["warmup_accepted"] = _accepted(insts)

        ingest_calls = _count_ingest_calls(insts[0]._peer_demuxes[sick])

        driver = _Driver(fwd, tokens_by_owner,
                         period_s=0.03 if args.smoke else 0.02)
        driver.start()

        def run_phase(name, setup=None):
            if setup:
                setup()
            a0 = _accepted(insts)
            s0 = driver.snapshot()
            c0 = ingest_calls["events.ingest"]
            t0 = time.perf_counter()
            time.sleep(phase_s)
            dt = time.perf_counter() - t0
            a1 = _accepted(insts)
            s1 = driver.snapshot()
            healthy_goodput = sum(a1[p] - a0[p]
                                  for p in range(N_HOSTS) if p != sick) / dt
            phase = {
                "wall_s": round(dt, 2),
                "sent_rows": {str(p): s1[p] - s0[p] for p in s1},
                "accepted_delta": [a1[i] - a0[i] for i in range(N_HOSTS)],
                "healthy_goodput_rows_s": round(healthy_goodput, 1),
                "sick_ingest_attempts": ingest_calls["events.ingest"] - c0,
                "pending_to_sick": fwd.pending_for(sick),
                "health": fwd.health.snapshot().get(str(sick)),
            }
            report["phases"][name] = phase
            return phase

        # -- phase 1: baseline -------------------------------------------
        baseline = run_phase("baseline")
        if baseline["healthy_goodput_rows_s"] <= 0:
            failures.append("baseline produced no goodput — bench broken")

        # -- phase 2: host 2 forced into SHEDDING ------------------------
        shed = run_phase(
            "shed",
            setup=lambda: insts[sick].overload.force(
                OverloadState.SHEDDING, reason="fleet-chaos"))
        # host 0's table must learn the state (heartbeat or piggyback —
        # either may land after the phase window, so wait for it)
        if not _until(lambda: fwd.health.overload_state(sick)
                      == int(OverloadState.SHEDDING), drain_timeout_s):
            failures.append(
                "health table never learned the SHEDDING state "
                f"(saw {fwd.health.overload_state(sick)})")
        # the device-facing edge reflects the OWNER's state: a purely
        # host-2-owned telemetry payload is refused with its hint
        edge = {"refused": False, "retry_after_s": None}
        try:
            fwd.ingest_payload(
                _payload(tokens_by_owner[sick][:4], 999_999),
                source_id="edge-check")
        except OverloadShed as e:
            edge = {"refused": True, "retry_after_s": e.retry_after_s,
                    "state": e.state.name}
        report["edge_refusal"] = edge
        if not edge["refused"]:
            failures.append("edge did not refuse a pure sick-owner payload "
                            "while the owner sheds")

        # -- phase 3: partition the sick host ----------------------------
        partition = run_phase(
            "partition",
            setup=lambda: faults.net_inject(sick_ep, drop=1.0))
        # the detector moves on missed heartbeats and failed sends, which
        # a loaded host may still be counting when the window closes
        _until(lambda: fwd.health.state(sick).name != "ALIVE",
               drain_timeout_s)
        state_after = fwd.health.state(sick).name
        report["state_after_partition"] = state_after
        if state_after == "ALIVE":
            failures.append("partitioned peer still ALIVE in the table")

        # -- phase 4: recover --------------------------------------------
        def heal():
            faults.net_clear(sick_ep)
            insts[sick].overload.force(OverloadState.NORMAL,
                                       reason="fleet-chaos-recover")
        recover = run_phase("recover", setup=heal)

        driver.stop()
        # the spool must drain to ZERO once the peer is healthy again
        _until(lambda: fwd.pending_rows() == 0, drain_timeout_s,
               poke=fwd.flush)
        pending_final = fwd.pending_rows()
        report["pending_after_recovery"] = pending_final
        if pending_final != 0:
            failures.append(
                f"spool did not drain on recovery ({pending_final} rows)")

        # -- contract checks ---------------------------------------------
        # 1. bounded attempts while unhealthy: paced probes, not a storm.
        #    Budget = one probe per interval + discovery slack per phase.
        for name in ("shed", "partition"):
            attempts = report["phases"][name]["sick_ingest_attempts"]
            budget = math.ceil(phase_s / probe_interval_s) + 8
            report["phases"][name]["attempt_budget"] = budget
            if attempts > budget:
                failures.append(
                    f"{name}: {attempts} send attempts to the unhealthy "
                    f"peer (budget {budget}) — retry storm")
        # 2. smooth degradation: healthy goodput never collapses
        floor = collapse_frac * baseline["healthy_goodput_rows_s"]
        for name in ("shed", "partition", "recover"):
            gp = report["phases"][name]["healthy_goodput_rows_s"]
            if gp < floor:
                failures.append(
                    f"{name}: healthy goodput collapsed "
                    f"({gp:.0f} < {floor:.0f} rows/s)")
        # 3. zero forward-plane dead letters (everything replayable)
        dead = int(fwd.dead_lettered)
        report["forward_dead_lettered"] = dead
        if dead:
            failures.append(f"{dead} rows dead-lettered by the forwarder")
        # 4. no flapping: the sick peer's table entry moved a bounded
        #    number of times across the whole script
        transitions = fwd.health.transitions(sick)
        report["sick_transitions"] = transitions
        if transitions > 8:
            failures.append(
                f"health table flapped: {transitions} transitions")
        # 5. at-least-once: after recovery + drain, the sick host holds
        #    every row sent its way (duplicates allowed, loss is not)
        insts[sick].dispatcher.flush()
        sick_accepted = _accepted(insts)[sick]
        sick_sent = driver.snapshot()[sick]
        report["sick_sent_rows"] = sick_sent
        report["sick_accepted_rows"] = sick_accepted
        if sick_accepted < sick_sent:
            failures.append(
                f"rows lost toward the sick host: sent {sick_sent}, "
                f"accepted {sick_accepted}")
        report["forward_metrics"] = {
            k: v for k, v in fwd.metrics().items() if k != "peers"}
        report["ok"] = not failures
        print(json.dumps(report, indent=2))
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(report, f, indent=2)
    finally:
        faults.net_clear()
        if driver is not None and driver.is_alive():
            driver.stop()
        for inst in insts:
            try:
                inst.stop()
                inst.terminate()
            except Exception:   # noqa: BLE001 — teardown best-effort
                pass
        shutil.rmtree(root, ignore_errors=True)

    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print("fleet_chaos: goodput degraded smoothly, spool drained, "
          "zero dead letters")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Flight recorder + SLO burn engine + on-device telemetry (ISSUE 9).

Covers the continuous-profiling subsystem end to end:

- :class:`FlightRecorder` ring/snapshot/rate-limit semantics and the
  JSONL round trip (``parse_snapshot`` validates);
- :class:`BurnRateEngine` multi-window burn evaluation with a fake
  clock (alerts arm on sustained breach in BOTH windows, clear on
  recovery, idle never burns);
- anomaly-overlap tail retention in :class:`Tracer` (satellite: traces
  overlapping an overload transition are ALWAYS kept);
- sub-millisecond histogram buckets (satellite: µs-scale host stages
  must not collapse into the old 1 ms bottom bucket);
- OpenMetrics round trip + name lint for the new ``device.*``,
  ``slo.*`` and ``flightrec.*`` families;
- the acceptance claim: with telemetry enabled, ``host_syncs`` stays
  1/K per ring — the occupancy block rides the existing shared fetch.
"""

import json
import os
import time

import numpy as np
import pytest

from sitewhere_tpu.runtime.flightrec import FlightRecorder, parse_snapshot
from sitewhere_tpu.runtime.metrics import (
    DEFAULT_LATENCY_BUCKETS_S,
    BurnRateEngine,
    Histogram,
    METRIC_NAME_RE,
    MetricsRegistry,
    SloTargets,
    parse_exposition,
    render_openmetrics,
)
from sitewhere_tpu.runtime.tracing import Tracer


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = FlightRecorder(data_dir=None, capacity=8)
        for i in range(20):
            rec.record(seq=i, commit="ok")
        recent = rec.recent(100)
        assert len(recent) == 8
        assert [r["seq"] for r in recent] == list(range(12, 20))
        assert rec.stats()["records_total"] == 20

    def test_snapshot_round_trips_through_parse(self, tmp_path):
        rec = FlightRecorder(data_dir=str(tmp_path), capacity=16)
        for i in range(5):
            rec.record(seq=i, rows=64, commit="ok", overload="NORMAL")
        path = rec.snapshot("unit-test", detail="because")
        assert path is not None and os.path.exists(path)
        snap = parse_snapshot(open(path, "rb").read())
        assert snap["header"]["reason"] == "unit-test"
        assert snap["header"]["detail"] == "because"
        assert len(snap["records"]) == 5
        assert snap["records"][-1]["seq"] == 4
        # the inventory lists it with its header fields
        names = {s["name"]: s for s in rec.snapshots()}
        assert os.path.basename(path) in names
        assert names[os.path.basename(path)]["records"] == 5

    def test_anomaly_dump_is_rate_limited(self, tmp_path):
        clock = FakeClock()
        rec = FlightRecorder(data_dir=str(tmp_path),
                             min_snapshot_interval_s=5.0, clock=clock)
        rec.record(seq=0, commit="ok")
        assert rec.anomaly("storm") is not None
        # the storm that follows is counted but produces no more files
        for _ in range(10):
            assert rec.anomaly("storm") is None
        stats = rec.stats()
        assert stats["anomalies"] == 11
        assert stats["snapshots_written"] == 1
        assert stats["suppressed_dumps"] == 10
        # past the window the next anomaly dumps again
        clock.advance(5.1)
        assert rec.anomaly("storm") is not None
        # explicit snapshots bypass the limit entirely
        assert rec.snapshot("manual") is not None

    def test_snapshots_prune_to_bound(self, tmp_path):
        rec = FlightRecorder(data_dir=str(tmp_path), max_snapshots=3)
        rec.record(seq=1, commit="ok")
        for i in range(6):
            rec.snapshot(f"dump-{i}")
        names = [s["name"] for s in rec.snapshots()]
        assert len(names) == 3
        # newest survive, file sequence keeps counting
        assert names[-1].startswith("000005-")

    def test_stall_dumps_are_pruned_apart_from_the_rest(self, tmp_path):
        """A run of routine stall dumps never prunes a crash's evidence,
        and the rest never prune the stall dumps."""
        rec = FlightRecorder(data_dir=str(tmp_path), max_snapshots=2)
        rec.record(seq=1, commit="ok")
        crash = os.path.basename(rec.snapshot("egress-crash"))
        for _ in range(5):
            rec.snapshot("stall")
        names = [s["name"] for s in rec.snapshots()]
        assert crash in names
        assert sum(n.endswith("-stall.jsonl") for n in names) == 2
        for i in range(3):
            rec.snapshot(f"overload-{i}")
        names = [s["name"] for s in rec.snapshots()]
        assert sum(n.endswith("-stall.jsonl") for n in names) == 2
        assert len(names) == 4 and crash not in names

    def test_rate_limit_is_per_reason(self, tmp_path):
        """An egress crash must never lose its dump because an
        unrelated overload transition dumped moments earlier."""
        clock = FakeClock()
        rec = FlightRecorder(data_dir=str(tmp_path),
                             min_snapshot_interval_s=5.0, clock=clock)
        rec.record(seq=0, commit="ok")
        assert rec.anomaly("overload-degraded") is not None
        assert rec.anomaly("egress-crash") is not None   # not suppressed
        assert rec.anomaly("egress-crash") is None       # same reason is
        assert rec.stats()["suppressed_dumps"] == 1

    def test_max_snapshots_zero_means_unlimited(self, tmp_path):
        rec = FlightRecorder(data_dir=str(tmp_path), max_snapshots=0)
        rec.record(seq=0, commit="ok")
        paths = [rec.snapshot(f"dump-{i}") for i in range(4)]
        assert all(p and os.path.exists(p) for p in paths)
        assert len(rec.snapshots()) == 4

    def test_recent_zero_limit_returns_nothing(self):
        rec = FlightRecorder(data_dir=None)
        for i in range(4):
            rec.record(seq=i, commit="ok")
        assert rec.recent(0) == []
        assert rec.recent(-3) == []
        assert len(rec.recent(2)) == 2

    def test_failed_snapshot_write_returns_the_rate_limit_slot(
            self, tmp_path):
        import shutil

        clock = FakeClock()
        rec = FlightRecorder(data_dir=str(tmp_path),
                             min_snapshot_interval_s=60.0, clock=clock)
        rec.record(seq=0, commit="ok")
        # break the snapshot dir: a FILE where the directory was
        shutil.rmtree(rec.dir)
        with open(rec.dir, "w") as f:
            f.write("x")
        assert rec.anomaly("disk-broken") is None
        os.unlink(rec.dir)
        os.makedirs(rec.dir)
        # same episode, SAME reason, write path repaired: the slot was
        # given back, so the retry dumps instead of being suppressed
        assert rec.anomaly("disk-broken") is not None

    def test_memory_only_recorder_never_snapshots(self):
        rec = FlightRecorder(data_dir=None)
        rec.record(seq=0, commit="ok")
        assert rec.snapshot("x") is None
        assert rec.anomaly("x") is None
        assert rec.snapshots() == []

    def test_read_snapshot_rejects_path_tricks(self, tmp_path):
        rec = FlightRecorder(data_dir=str(tmp_path))
        rec.record(seq=0, commit="ok")
        path = rec.snapshot("ok")
        assert rec.read_snapshot(os.path.basename(path))
        for bad in ("../secrets.jsonl", "/etc/passwd",
                    "missing.jsonl", "000000-ok.txt"):
            with pytest.raises(KeyError):
                rec.read_snapshot(bad)

    def test_reason_is_sanitized_into_the_filename(self, tmp_path):
        rec = FlightRecorder(data_dir=str(tmp_path))
        path = rec.snapshot("SLO/../p99 breach!")
        name = os.path.basename(path)
        assert "/" not in name.replace(".jsonl", "")
        assert ".." not in name
        assert name.endswith(".jsonl")

    def test_sequence_resumes_after_restart(self, tmp_path):
        rec = FlightRecorder(data_dir=str(tmp_path))
        first = rec.snapshot("boot")
        rec2 = FlightRecorder(data_dir=str(tmp_path))
        second = rec2.snapshot("after-restart")
        assert os.path.basename(second) > os.path.basename(first)
        assert os.path.exists(first)   # never overwritten

    def test_parse_snapshot_validates(self):
        with pytest.raises(ValueError):
            parse_snapshot(b"")
        with pytest.raises(ValueError):
            parse_snapshot(b'{"kind": "other"}\n')
        # count mismatch: header promises 2, file holds 1
        bad = (json.dumps({"kind": "flightrec-snapshot", "reason": "x",
                           "ts": 0, "records": 2}) + "\n"
               + json.dumps({"seq": 1}) + "\n").encode()
        with pytest.raises(ValueError):
            parse_snapshot(bad)


class TestTimelineRenderer:
    def test_renders_a_snapshot(self, tmp_path, capsys):
        import importlib.util

        rec = FlightRecorder(data_dir=str(tmp_path))
        rec.record(seq=1, rows=64, fill=1.0, slot=0, wait_ms=1.0,
                   dispatch_ms=0.5, egress_ms=0.5, e2e_ms=4.0,
                   overload="NORMAL", commit="ok")
        rec.record(seq=2, rows=64, fill=1.0, slot=1, wait_ms=1.0,
                   dispatch_ms=0.5, egress_ms=0.0, e2e_ms=4.0,
                   overload="DEGRADED", commit="failed",
                   error="ValueError: boom")
        # kind-style EVENT records interleave with the batch rows: the
        # watchdog's hung-step dump and the nonfinite scan's quarantine
        # strike (the device-fault containment plane's cold paths)
        rec.record(kind="hung-step", seq=3, rows=64, reason="fill",
                   slot=0)
        rec.record(kind="quarantine", seq=3, rows=2, devices=[7, 9],
                   strikes=3)
        path = rec.snapshot("egress-crash")

        tool = os.path.join(os.path.dirname(__file__), os.pardir,
                            "tools", "flightrec_timeline.py")
        spec = importlib.util.spec_from_file_location(
            "flightrec_timeline", tool)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.main([path]) == 0
        out = capsys.readouterr().out
        assert "egress-crash" in out
        assert "!!failed" in out
        assert "ValueError: boom" in out
        assert "** hung-step" in out
        assert "** quarantine" in out
        assert "devices=[7, 9]" in out
        assert "2 batches shown, 1 failed commits, 2 events" in out


# ---------------------------------------------------------------------------
# SLO burn-rate engine
# ---------------------------------------------------------------------------

def _engine(clock, **kw):
    alerts = []
    kw.setdefault("targets", SloTargets(throughput_eps=1000.0,
                                        p99_ms=10.0, shed_rate=0.01))
    kw.setdefault("windows_s", (10.0, 60.0))
    kw.setdefault("error_budget", 0.5)
    kw.setdefault("alert_burn", 2.0)
    kw.setdefault("min_samples", 3)
    eng = BurnRateEngine(metrics=MetricsRegistry(), clock=clock,
                         on_alert=lambda n, b: alerts.append((n, b)),
                         **kw)
    return eng, alerts


GOOD = {"events": 2000, "elapsed_s": 1.0, "p99_ms": 5.0,
        "shed": 0, "admitted": 2000}
BAD_P99 = {"events": 2000, "elapsed_s": 1.0, "p99_ms": 50.0,
           "shed": 0, "admitted": 2000}


class TestBurnRateEngine:
    def test_healthy_traffic_never_alerts(self):
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for _ in range(30):
            eng.observe(GOOD, clock.advance(1.0))
        assert alerts == []
        snap = eng.snapshot()
        assert snap["objectives"]["p99_ms"]["burn_fast"] == 0.0
        assert not snap["objectives"]["p99_ms"]["alerting"]

    def test_sustained_breach_arms_once_then_clears(self):
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for _ in range(10):
            eng.observe(BAD_P99, clock.advance(1.0))
        # breach fraction 1.0 / budget 0.5 = burn 2.0 in both windows
        assert [name for name, _ in alerts] == ["p99_ms"]
        assert eng.snapshot()["objectives"]["p99_ms"]["alerting"]
        # still breaching: armed once, not re-fired per sample
        for _ in range(5):
            eng.observe(BAD_P99, clock.advance(1.0))
        assert len(alerts) == 1
        # recovery: fast window drains below burn 1.0 and the alert clears
        for _ in range(20):
            eng.observe(GOOD, clock.advance(1.0))
        assert not eng.snapshot()["objectives"]["p99_ms"]["alerting"]

    def test_armed_alert_clears_when_traffic_stops(self):
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for _ in range(10):
            eng.observe(BAD_P99, clock.advance(1.0))
        assert eng.snapshot()["objectives"]["p99_ms"]["alerting"]
        # traffic stops ENTIRELY: every verdict is None, but time still
        # passes — the stale breach samples must age out of the fast
        # window and the alert must clear, not stick forever
        idle = {"events": 0, "elapsed_s": 1.0, "p99_ms": None,
                "shed": 0, "admitted": 0}
        for _ in range(15):
            eng.observe(idle, clock.advance(1.0))
        snap = eng.snapshot()["objectives"]["p99_ms"]
        assert snap["samples_fast"] == 0
        assert not snap["alerting"]

    def test_min_samples_gates_a_blip(self):
        clock = FakeClock()
        eng, alerts = _engine(clock, min_samples=5)
        for _ in range(3):
            eng.observe(BAD_P99, clock.advance(1.0))
        assert alerts == []   # three samples is a blip, not a burn

    def test_idle_is_not_burn(self):
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for _ in range(10):
            # no events, no latency sample, nothing offered: every
            # objective lacks evidence — windows must stay empty
            eng.observe({"events": 0, "elapsed_s": 1.0, "p99_ms": None,
                         "shed": 0, "admitted": 0}, clock.advance(1.0))
        snap = eng.snapshot()
        assert alerts == []
        for obj in snap["objectives"].values():
            assert obj["samples_fast"] == 0

    def test_throughput_and_shed_objectives(self):
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for _ in range(70):   # past the slow window span, as above
            # completion (100 ev/s) far behind offered load (500 ev/s)
            # -> deficit outgrows the lag tolerance (throughput
            # breach); 10% shed over the 1% budget
            eng.observe({"events": 100, "elapsed_s": 1.0, "p99_ms": 1.0,
                         "shed": 50, "admitted": 450}, clock.advance(1.0))
        assert {name for name, _ in alerts} == {"throughput_eps",
                                                "shed_rate"}

    def test_shedding_episode_is_not_a_throughput_deficit(self):
        """Shed rows are refused at intake — they can never complete,
        so they must not accumulate as unserved demand that pins the
        throughput alert forever after the episode ends."""
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for _ in range(70):
            # every ADMITTED row completes; 10% is shed (DEGRADED)
            eng.observe({"events": 450, "elapsed_s": 1.0, "p99_ms": 1.0,
                         "shed": 50, "admitted": 450}, clock.advance(1.0))
        assert {name for name, _ in alerts} == {"shed_rate"}
        # recovery: healthy traffic must show a clean throughput burn
        for _ in range(70):
            eng.observe({"events": 500, "elapsed_s": 1.0, "p99_ms": 1.0,
                         "shed": 0, "admitted": 500}, clock.advance(1.0))
        obj = eng.snapshot()["objectives"]["throughput_eps"]
        assert obj["burn_fast"] == 0.0 and not obj["alerting"]

    def test_sub_target_offered_load_fully_served_is_healthy(self):
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for _ in range(20):
            # demand (500 ev/s) well under the 1000 capacity target but
            # FULLY served — meeting demand is never a breach
            eng.observe({"events": 500, "elapsed_s": 1.0, "p99_ms": 1.0,
                         "shed": 0, "admitted": 500}, clock.advance(1.0))
        assert alerts == []

    def test_total_stall_is_a_throughput_breach_not_idle(self):
        clock = FakeClock()
        eng, alerts = _engine(clock)
        # past the slow window's span, so the deficit's brief pre-
        # tolerance grace ages out of BOTH windows
        for _ in range(70):
            # wedged pipeline: nothing completes while intake keeps
            # admitting — the running deficit grows past the lag
            # tolerance and judges as a stall, never as idle
            eng.observe({"events": 0, "elapsed_s": 1.0, "p99_ms": None,
                         "shed": 0, "admitted": 2000},
                        clock.advance(1.0))
        assert [name for name, _ in alerts] == ["throughput_eps"]

    def test_backlog_witnesses_a_stall_without_admission_counters(self):
        """Deployments without the overload controller alias admitted
        to processed, so a wedge shows offered == events == 0 — the
        queue-backlog snapshot is the stall witness, and it must not
        leave a residual deficit that pins the alert after recovery."""
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for _ in range(70):
            eng.observe({"events": 0, "elapsed_s": 1.0, "p99_ms": None,
                         "shed": 0, "admitted": 0, "backlog": 500},
                        clock.advance(1.0))
        assert [name for name, _ in alerts] == ["throughput_eps"]
        # recovery: backlog drained, traffic flows fully served — the
        # alert clears instead of being pinned by stall-era bookkeeping
        for _ in range(15):
            eng.observe({"events": 500, "elapsed_s": 1.0, "p99_ms": 1.0,
                         "shed": 0, "admitted": 500, "backlog": 0},
                        clock.advance(1.0))
        snap = eng.snapshot()["objectives"]["throughput_eps"]
        assert not snap["alerting"]

    def test_bursty_chain_granularity_egress_is_not_a_breach(self):
        """A K-deep ring lands ~K·width rows per chain, so per-sample
        completion deltas alternate 0 / 2× offered — the deficit's lag
        tolerance must absorb one chain in flight without burning."""
        clock = FakeClock()
        eng, alerts = _engine(clock)
        for i in range(30):
            eng.observe({"events": 0 if i % 2 == 0 else 2000,
                         "elapsed_s": 1.0, "p99_ms": 1.0,
                         "shed": 0, "admitted": 1000},
                        clock.advance(1.0))
        assert alerts == []

    def test_zero_target_disables_throughput(self):
        clock = FakeClock()
        eng, alerts = _engine(clock, targets=SloTargets(
            throughput_eps=0.0, p99_ms=10.0, shed_rate=0.01))
        for _ in range(10):
            eng.observe({"events": 10, "elapsed_s": 1.0, "p99_ms": 1.0,
                         "shed": 0, "admitted": 10}, clock.advance(1.0))
        assert alerts == []

    def test_burn_gauges_and_alert_span(self):
        clock = FakeClock()
        reg = MetricsRegistry()
        tracer = Tracer(sample_rate=1.0)
        eng = BurnRateEngine(
            targets=SloTargets(throughput_eps=0.0),
            windows_s=(10.0, 60.0), error_budget=0.5, alert_burn=2.0,
            min_samples=3, metrics=reg, tracer=tracer, clock=clock)
        # families pre-registered at burn 0 (scrape surface contract)
        assert "slo.burn_rate.p99_ms.fast" in reg.names()
        assert "slo.alert.p99_ms" in reg.names()
        for _ in range(5):
            eng.observe(BAD_P99, clock.advance(1.0))
        snap = reg.snapshot()
        assert snap["gauges"]["slo.burn_rate.p99_ms.fast"] == 2.0
        assert snap["gauges"]["slo.alert.p99_ms"] == 1
        spans = [s for s in tracer.recent(50)
                 if s["name"] == "slo.p99_ms_arm"]
        assert spans and spans[0]["tags"]["burn_fast"] >= 2.0

    def test_tick_pulls_from_sample_fn_rate_limited(self):
        clock = FakeClock()
        samples = []

        def sample_fn():
            samples.append(1)
            return GOOD

        eng = BurnRateEngine(sample_fn=sample_fn, sample_interval_s=1.0,
                             metrics=MetricsRegistry(), clock=clock)
        eng.tick()
        eng.tick()           # same instant: rate-limited away
        clock.advance(1.5)
        eng.tick()
        assert len(samples) == 2


# ---------------------------------------------------------------------------
# anomaly-overlap tail retention (satellite)
# ---------------------------------------------------------------------------

class TestAnomalyTailRetention:
    def test_trace_overlapping_anomaly_is_retained(self):
        tracer = Tracer(sample_rate=0.0, tail_errors=True,
                        tail_anomaly_window_s=30.0)
        trace = tracer.trace("pipeline.plan")
        with trace.span("step.dispatch"):
            pass
        tracer.note_anomaly()   # the overload transition lands mid-trace
        with trace.span("egress.persist"):
            pass
        trace.end()
        assert tracer.retained_anomaly == 1
        assert tracer.retained_tail == 1
        assert any(s["name"] == "step.dispatch"
                   for s in tracer.recent(10))

    def test_clean_fast_trace_outside_window_still_drops(self):
        tracer = Tracer(sample_rate=0.0, tail_errors=True,
                        tail_anomaly_window_s=5.0)
        # anomaly far in the past: this trace starts way after its window
        tracer.note_anomaly(ts=time.time() - 1000.0)
        trace = tracer.trace("pipeline.plan")
        with trace.span("step.dispatch"):
            pass
        trace.end()
        assert tracer.retained_anomaly == 0
        assert tracer.dropped_tail == 1

    def test_trace_started_within_window_after_anomaly_is_retained(self):
        tracer = Tracer(sample_rate=0.0, tail_errors=True,
                        tail_anomaly_window_s=30.0)
        tracer.note_anomaly()   # transition fires FIRST
        trace = tracer.trace("pipeline.plan")   # plan right after it
        with trace.span("step.dispatch"):
            pass
        trace.end()
        assert tracer.retained_anomaly == 1

    def test_overload_transition_stamps_the_tracer(self):
        from sitewhere_tpu.runtime.overload import (
            OverloadController,
            OverloadState,
        )

        tracer = Tracer(sample_rate=0.0, tail_errors=True)
        ctl = OverloadController(metrics=MetricsRegistry(), tracer=tracer,
                                 clock=FakeClock())
        ctl.force(OverloadState.SHEDDING, "test")
        assert tracer.anomalies_noted == 1
        assert tracer.stats()["anomalies_noted"] == 1


# ---------------------------------------------------------------------------
# sub-millisecond buckets (satellite)
# ---------------------------------------------------------------------------

class TestSubMillisecondBuckets:
    def test_default_buckets_resolve_microsecond_stages(self):
        assert DEFAULT_LATENCY_BUCKETS_S[0] < 0.001
        sub_ms = [b for b in DEFAULT_LATENCY_BUCKETS_S if b < 0.001]
        assert len(sub_ms) >= 3

    def test_us_scale_observations_do_not_collapse(self):
        h = Histogram()
        h.observe(0.00008)    # an 80µs host stage
        h.observe(0.0004)     # a 400µs host stage
        h.observe(0.0079)     # the 7.9ms device step
        snap = h.snapshot()["buckets"]
        # each lands in a DIFFERENT bucket: cumulative counts step at
        # distinct bounds instead of all three hitting le=0.001 together
        assert snap[0.0001] == 1
        assert snap[0.0005] == 2
        assert snap[0.001] == 2
        assert snap[0.01] == 3


# ---------------------------------------------------------------------------
# OpenMetrics round trip + name lint for the new families (satellite)
# ---------------------------------------------------------------------------

class TestNewFamiliesExposition:
    def test_device_slo_flightrec_families_round_trip(self, tmp_path):
        from sitewhere_tpu.pipeline.telemetry import (
            DEVICE_STAGE_MS_BUCKETS,
        )

        reg = MetricsRegistry()
        # device.* (occupancy gauges + stage histograms + cost gauges)
        reg.gauge("device.occupancy.rows_admitted").set(512)
        reg.gauge("device.occupancy.presence_merges").set(17)
        h = reg.histogram("device.stage_ms.full",
                          buckets=DEVICE_STAGE_MS_BUCKETS)
        h.observe(7.9)
        reg.gauge("device.cost.flops").set(1.5e9)
        # slo.* / flightrec.* via their real owners
        BurnRateEngine(metrics=reg, clock=FakeClock())
        rec = FlightRecorder(data_dir=str(tmp_path), metrics=reg)
        rec.record(seq=0, commit="ok")
        rec.anomaly("lint")

        # every registered name obeys the linted dotted convention AND
        # the swlint family registry (closed memberships for
        # device.occupancy/device.cost/flightrec, governed device./slo.
        # prefixes) — one contract shared with the static pass
        from sitewhere_tpu.analysis.metric_names import lint_names

        for name in reg.names():
            assert METRIC_NAME_RE.match(name), name
        assert lint_names(reg.names()) == []

        families = parse_exposition(render_openmetrics(reg))
        assert families["device_occupancy_rows_admitted"]["samples"][
            "device_occupancy_rows_admitted"] == 512
        assert families["device_stage_ms_full"]["type"] == "histogram"
        assert families["device_stage_ms_full"]["samples"][
            "device_stage_ms_full_count"] == 1
        assert families["slo_burn_rate_p99_ms_fast"]["type"] == "gauge"
        assert families["flightrec_records"]["samples"][
            "flightrec_records_total"] == 1
        assert families["flightrec_snapshots"]["samples"][
            "flightrec_snapshots_total"] == 1

    def test_stage_histogram_buckets_catch_the_device_step(self):
        from sitewhere_tpu.pipeline.telemetry import (
            DEVICE_STAGE_MS_BUCKETS,
        )

        h = Histogram(buckets=DEVICE_STAGE_MS_BUCKETS)
        h.observe(7.9)    # the r05 device step, in ms
        h.observe(0.05)   # a µs-scale stage
        snap = h.snapshot()["buckets"]
        assert snap[10.0] == 2
        assert snap[0.05] == 1
        assert snap[5.0] == 1


# ---------------------------------------------------------------------------
# packed telemetry block (tentpole: the occupancy counters themselves)
# ---------------------------------------------------------------------------

class TestPackedTelemetryBlock:
    def test_occupancy_counters_match_numpy_reference(self):
        import jax
        import jax.numpy as jnp

        from sitewhere_tpu.pipeline.packed import (
            BATCH_F,
            BATCH_I,
            PackedView,
            pack_batch_host,
            pack_state,
            pack_tables,
            packed_pipeline_step,
        )
        from sitewhere_tpu.schema import (
            DeviceState,
            Registry,
            RuleTable,
            ZoneTable,
        )

        cap, width = 64, 48
        registry = Registry.empty(cap).replace(
            active=jnp.arange(cap) < 16,
            assignment_status=(jnp.arange(cap) < 16).astype(jnp.int32),
            # tenant isolation: the batch carries tenant 0, so the
            # registry rows must too (empty() defaults to -1)
            tenant_id=jnp.zeros(cap, jnp.int32))
        tables = pack_tables(registry, RuleTable.empty(4),
                             ZoneTable.empty(4))
        ps = pack_state(DeviceState.empty(cap))
        rng = np.random.default_rng(7)
        cols = {f: np.zeros(width, np.int32) for f in BATCH_I}
        for f in BATCH_F:
            cols[f] = np.zeros(width, np.float32)
        cols["valid"] = (rng.random(width) < 0.75).astype(np.int32)
        cols["device_id"] = rng.integers(0, 32, width).astype(np.int32)
        cols["ts_s"] = np.full(width, 1_753_800_000, np.int32)
        cols["update_state"] = (rng.random(width) < 0.5).astype(np.int32)
        bi, bf = pack_batch_host(cols, width)
        step = jax.jit(packed_pipeline_step)
        _, oi, mets, present = step(tables, ps, jnp.asarray(bi),
                                    jnp.asarray(bf))
        view = PackedView(oi, mets, present)
        tel = view.telemetry
        assert tel["rows_invalid"] == width - int(view.metrics.processed)
        assert tel["state_writes"] == int(
            (view.accepted & cols["update_state"].astype(bool)).sum())
        assert tel["presence_merges"] == int(
            np.asarray(present).sum())
        # and some rows genuinely exercised each counter
        assert 0 < tel["rows_invalid"] < width
        assert tel["state_writes"] > 0
        assert tel["presence_merges"] > 0

    def test_stub_12_wide_metrics_vector_yields_empty_telemetry(self):
        # older stubs (tests composing bare views) must not crash
        from sitewhere_tpu.pipeline.packed import (
            METRIC_SCALARS,
            PackedView,
        )
        from sitewhere_tpu.pipeline.step import NUM_EVENT_TYPES

        mets = np.zeros(len(METRIC_SCALARS) + NUM_EVENT_TYPES, np.int32)
        view = PackedView(np.zeros((10, 4), np.int32), mets, None)
        assert view.telemetry == {}


# ---------------------------------------------------------------------------
# acceptance: telemetry adds ZERO host syncs + the REST surface serves it
# ---------------------------------------------------------------------------

def _ring_instance(tmp_path, width=64):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    return Instance(Config({
        "instance": {"id": "flightrec-smoke",
                     "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": width, "registry_capacity": 128,
                     "mtype_slots": 4, "deadline_ms": 60_000.0,
                     "n_shards": 1, "ring_depth": 2},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
    }, apply_env=False))


class TestTelemetryZeroSyncAcceptance:
    def test_host_syncs_stay_one_per_chain_with_telemetry_on(
            self, tmp_path):
        """ISSUE 9 acceptance: with the occupancy telemetry + flight
        recorder + SLO engine all enabled (the instance defaults), the
        forced-ring path still pays exactly ONE blocking sync per
        K-step chain — the telemetry block rides the shared fetch."""
        import json as _json

        inst = _ring_instance(tmp_path)
        width = 64
        inst.start()
        try:
            assert inst.flightrec is not None and inst.slo is not None
            inst.device_management.create_device_type(
                token="sensor", name="Sensor")
            for i in range(width):
                inst.device_management.create_device(
                    token=f"d-{i}", device_type="sensor")
                inst.device_management.create_device_assignment(
                    device=f"d-{i}")

            def payload(r):
                return "\n".join(_json.dumps({
                    "deviceToken": f"d-{i}", "type": "Measurement",
                    "request": {"name": "temp", "value": 1.0 + i,
                                "eventDate": 1_753_800_000 + r},
                }) for i in range(width)).encode()

            for r in range(4):
                inst.dispatcher.ingest_wire_lines(payload(r))
            inst.dispatcher.flush()
            snap = inst.dispatcher.metrics_snapshot()
            assert snap["steps"] == 4 and snap["ring_chains"] == 2
            # THE acceptance number: 2 chains -> 2 syncs, 1/K per batch
            assert snap["host_syncs"] == 2
            # and the telemetry really landed from those same fetches
            gauges = inst.metrics.snapshot()["gauges"]
            assert gauges["device.occupancy.rows_admitted"] == width
            assert gauges["device.occupancy.presence_merges"] > 0
            assert gauges["device.occupancy.rows_invalid"] == 0
            # flight records exist for every batch, slots attributed
            records = inst.flightrec.recent(10)
            assert len(records) == 4
            assert {r["slot"] for r in records} == {0, 1}
            assert all(r["commit"] == "ok" for r in records)
            assert all(r["dispatch_ms"] > 0 for r in records)

            # a PARTIAL plan (10 rows, width 64): the gauge must read
            # zero lost rows, not ~54 rows of batch padding
            partial = "\n".join(_json.dumps({
                "deviceToken": f"d-{i}", "type": "Measurement",
                "request": {"name": "temp", "value": 2.0,
                            "eventDate": 1_753_800_010}})
                for i in range(10)).encode()
            inst.dispatcher.ingest_wire_lines(partial)
            inst.dispatcher.flush()
            gauges = inst.metrics.snapshot()["gauges"]
            assert gauges["device.occupancy.rows_admitted"] == 10
            assert gauges["device.occupancy.rows_invalid"] == 0
        finally:
            inst.stop()
            inst.terminate()

    def test_inline_egress_crash_is_recorded_too(self, tmp_path):
        """With egress offload OFF (the CPU-backend default) an egress
        crash runs inline on the dispatch thread — it must still leave
        a failed-commit record and an egress-crash snapshot."""
        import json as _json

        from sitewhere_tpu.instance import Instance
        from sitewhere_tpu.runtime import faults
        from sitewhere_tpu.runtime.config import Config

        width = 32
        inst = Instance(Config({
            "instance": {"id": "inline-crash",
                         "data_dir": str(tmp_path / "data")},
            "pipeline": {"width": width, "registry_capacity": 64,
                         "mtype_slots": 4, "deadline_ms": 60_000.0,
                         "n_shards": 1, "egress_offload": False},
            "presence": {"scan_interval_s": 3600.0,
                         "missing_after_s": 1800},
        }, apply_env=False))
        inst.start()
        try:
            inst.device_management.create_device_type(
                token="sensor", name="Sensor")
            inst.device_management.create_device(
                token="d-0", device_type="sensor")
            inst.device_management.create_device_assignment(device="d-0")
            payload = _json.dumps({
                "deviceToken": "d-0", "type": "Measurement",
                "request": {"name": "temp", "value": 1.0,
                            "eventDate": 1_753_800_000}}).encode()
            faults.inject("dispatcher.egress", times=1)
            inst.dispatcher.ingest_wire_lines(payload)
            with pytest.raises(Exception):
                inst.dispatcher.flush(timeout_s=0.5)
            failed = [r for r in inst.flightrec.recent(20)
                      if r["commit"] == "failed"]
            assert failed and "error" in failed[0]
            assert any("egress-crash" in s["name"]
                       for s in inst.flightrec.snapshots())
        finally:
            faults.clear()
            inst.stop()
            inst.terminate()

    def test_rest_surface_serves_recorder_and_slo(self, tmp_path):
        from sitewhere_tpu.runtime.flightrec import parse_snapshot
        from sitewhere_tpu.web import WebServer

        inst = _ring_instance(tmp_path)
        inst.start()
        web = WebServer(inst)
        web.start()
        try:
            import urllib.request

            inst.flightrec.record(seq=9, rows=1, commit="ok")
            dump = inst.flightrec.snapshot("rest-test")
            token = inst.tokens.mint("admin", ["ROLE_ADMIN"])

            def get(path, raw=False):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{web.port}{path}",
                    headers={"Authorization": f"Bearer {token}"})
                with urllib.request.urlopen(req, timeout=10) as resp:
                    data = resp.read()
                return data if raw else json.loads(data)

            doc = get("/api/instance/flightrecorder")
            assert doc["stats"]["records_total"] >= 1
            assert any(r["seq"] == 9 for r in doc["records"])
            names = [s["name"] for s in doc["snapshots"]]
            assert os.path.basename(dump) in names
            snap = parse_snapshot(get(
                f"/api/instance/flightrecorder/snapshots/"
                f"{os.path.basename(dump)}", raw=True))
            assert snap["header"]["reason"] == "rest-test"

            slo = get("/api/instance/slo")
            assert slo["targets"]["p99_ms"] == 10.0
            assert "p99_ms" in slo["objectives"]

            topo = get("/api/instance/topology")
            assert "flightrec" in topo and "slo" in topo
        finally:
            web.stop()
            inst.stop()
            inst.terminate()

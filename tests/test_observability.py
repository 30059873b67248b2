"""Observability layer: metrics registry under load, fixed-bucket
histograms + OpenMetrics exposition, tail-based trace sampling, and the
metric-name lint contract (tier-1).

The tier-1 proof obligations from the observability PR:

- ``MetricsRegistry.snapshot`` is safe (and consistent per-instrument)
  under concurrent writers — no lost counter increments, no exceptions
  while writers hammer the registry mid-snapshot;
- ``Timer.observe`` is O(1) (bounded ring, lazy sort) but keeps the
  percentile/snapshot API bit-for-bit usable;
- the tail sampler ALWAYS retains error/slow traces and drops fast
  clean ones, deterministically under a seeded head-sampler RNG;
- ``render_openmetrics`` output round-trips through
  ``parse_exposition`` with bucket counts and exemplars intact;
- every metric name registered by a running instance follows the
  lowercase dotted ``subsystem.noun_verb`` convention (METRIC_NAME_RE).
"""

import threading
import time

import numpy as np
import pytest

from sitewhere_tpu.runtime.metrics import (
    DEFAULT_LATENCY_BUCKETS_S,
    METRIC_NAME_RE,
    Histogram,
    MetricsRegistry,
    Timer,
    global_registry,
    parse_exposition,
    render_openmetrics,
    sanitize_metric_name,
)
from sitewhere_tpu.runtime.tracing import Tracer

from time_limit import WAIT_S


# ---------------------------------------------------------------------------
# registry under concurrent writers
# ---------------------------------------------------------------------------

class TestSnapshotConcurrency:
    def test_snapshot_under_concurrent_writers(self):
        """Writers hammer counters/timers/histograms while the reader
        snapshots in a tight loop: nothing raises, intermediate
        snapshots are monotone, and the final counts are exact."""
        reg = MetricsRegistry()
        n_threads, n_iter = 8, 2000
        stop = threading.Event()
        errors = []

        def writer(k):
            try:
                c = reg.counter("load.events_written")
                t = reg.timer("load.write_latency_s")
                h = reg.histogram("load.write_hist_s")
                g = reg.gauge(f"load.queue_depth.w{k}")
                for i in range(n_iter):
                    c.inc()
                    t.observe(i * 1e-6)
                    h.observe(i * 1e-6, trace_id=f"t{k}-{i}")
                    g.set(i)
            except Exception as e:  # pragma: no cover - the failure path
                errors.append(e)

        def reader():
            last = 0
            try:
                while not stop.is_set():
                    snap = reg.snapshot()
                    cur = snap["counters"].get("load.events_written", 0)
                    assert cur >= last
                    last = cur
                    # percentile read races the lazy re-sort on purpose
                    reg.timer("load.write_latency_s").percentile(0.99)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(n_threads)]
        rt = threading.Thread(target=reader)
        rt.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT_S)
        stop.set()
        rt.join(WAIT_S)
        assert not any(t.is_alive() for t in threads + [rt])

        assert errors == []
        snap = reg.snapshot()
        assert snap["counters"]["load.events_written"] == n_threads * n_iter
        assert snap["timers"]["load.write_latency_s"]["count"] == \
            n_threads * n_iter
        assert snap["histograms"]["load.write_hist_s"]["count"] == \
            n_threads * n_iter

    def test_names_are_sanitized_on_access(self):
        reg = MetricsRegistry()
        c = reg.counter("Outbound.Queue Depth:kafka-1")
        assert c is reg.counter("outbound.queue_depth_kafka-1")
        for name in reg.names():
            assert METRIC_NAME_RE.match(name), name


# ---------------------------------------------------------------------------
# timer ring (satellite: O(n) insort -> O(1) append + lazy sort)
# ---------------------------------------------------------------------------

class TestTimerRing:
    def test_percentiles_survive_ring_overflow(self):
        t = Timer(reservoir=128)
        for v in range(1000):
            t.observe(v / 1000.0)
        # ring keeps the newest 128 samples: [0.872 .. 0.999]
        assert t.count == 1000
        assert t.percentile(0.0) == pytest.approx(0.872)
        assert t.percentile(0.99) >= 0.99
        assert t.mean == pytest.approx(sum(range(1000)) / 1000.0 / 1000.0)

    def test_sort_is_lazy_and_cache_invalidates(self):
        t = Timer(reservoir=16)
        t.observe(0.5)
        assert t.percentile(0.5) == 0.5
        t.observe(0.1)  # invalidates the cached sort
        assert t.percentile(0.0) == 0.1


# ---------------------------------------------------------------------------
# histograms + exposition round trip
# ---------------------------------------------------------------------------

class TestHistogramExposition:
    def test_bucket_counts_are_cumulative(self):
        h = Histogram(buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["buckets"] == {0.01: 1, 0.1: 2, 1.0: 3}
        assert snap["sum"] == pytest.approx(5.555)

    def test_exemplar_pins_last_trace_per_bucket(self):
        h = Histogram(buckets=(0.01, 0.1))
        h.observe(0.005, trace_id="aa")
        h.observe(0.006, trace_id="bb")
        h.observe(0.05)  # no exemplar for this bucket
        counts, count, total, exemplars = h._render_state()
        assert exemplars[0][0] == "bb"
        assert 1 not in exemplars

    def test_render_parse_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("pipeline.events_processed").inc(42)
        reg.gauge("ingest.queue_depth").set(7)
        tm = reg.timer("pipeline.step_latency_s")
        for v in (0.001, 0.002, 0.004):
            tm.observe(v)
        h = reg.histogram("pipeline.e2e_latency_s")
        h.observe(0.004, trace_id="deadbeef")
        h.observe(0.2)

        text = render_openmetrics(reg)
        fams = parse_exposition(text)

        assert fams["pipeline_events_processed"]["type"] == "counter"
        assert fams["pipeline_events_processed"]["samples"][
            "pipeline_events_processed_total"] == 42
        assert fams["ingest_queue_depth"]["samples"]["ingest_queue_depth"] == 7
        assert fams["pipeline_step_latency_s"]["type"] == "summary"
        hist = fams["pipeline_e2e_latency_s"]
        assert hist["type"] == "histogram"
        assert hist["samples"]['pipeline_e2e_latency_s_bucket{le="0.005"}'] == 1
        assert hist["samples"]['pipeline_e2e_latency_s_bucket{le="+Inf"}'] == 2
        assert hist["samples"]["pipeline_e2e_latency_s_count"] == 2
        # the exemplar is on the rendered bucket line
        assert 'trace_id="deadbeef"' in text

    def test_registry_merge_is_first_wins(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("pipeline.events_processed").inc(1)
        b.counter("pipeline.events_processed").inc(99)
        fams = parse_exposition(render_openmetrics(a, b))
        assert fams["pipeline_events_processed"]["samples"][
            "pipeline_events_processed_total"] == 1

    def test_non_finite_samples_do_not_break_the_scrape(self):
        # one inf/NaN sample must never 500 every subsequent scrape
        reg = MetricsRegistry()
        reg.gauge("pipeline.bad_inf").set(float("inf"))
        reg.gauge("pipeline.bad_nan").set(float("nan"))
        reg.histogram("pipeline.bad_hist_s").observe(float("inf"))
        text = render_openmetrics(reg)
        assert "pipeline_bad_inf +Inf" in text
        assert "pipeline_bad_nan NaN" in text
        fams = parse_exposition(text)
        assert fams["pipeline_bad_inf"]["samples"]["pipeline_bad_inf"] \
            == float("inf")

    def test_cross_kind_name_collision_warns_not_silently_hides(self, caplog):
        reg = MetricsRegistry()
        reg.counter("pipeline.clash").inc(5)
        reg.gauge("pipeline.clash").set(9)
        with caplog.at_level("WARNING", "sitewhere_tpu.metrics"):
            text = render_openmetrics(reg)
        assert "pipeline_clash_total 5" in text   # counter renders first
        assert any("hidden from exposition" in r.message
                   for r in caplog.records)

    def test_parser_validates(self):
        with pytest.raises(ValueError):
            parse_exposition("foo_total 1\n")  # no # EOF
        with pytest.raises(ValueError):
            parse_exposition("foo_total 1\n# EOF\n")  # no TYPE
        with pytest.raises(ValueError):
            parse_exposition("# TYPE foo\n# EOF\n")  # TYPE missing type


# ---------------------------------------------------------------------------
# tail-based sampling (deterministic via seeded head RNG)
# ---------------------------------------------------------------------------

class TestTailSampling:
    def _tracer(self, **kw):
        kw.setdefault("sample_rate", 0.0)  # head sampler never fires
        kw.setdefault("tail_errors", True)
        kw.setdefault("tail_latency_s", 0.05)
        kw.setdefault("seed", 7)
        return Tracer(**kw)

    def test_error_trace_is_always_retained(self):
        tr = self._tracer()
        trace = tr.trace("plan")
        with pytest.raises(RuntimeError):
            with trace.span("step.dispatch"):
                raise RuntimeError("boom")
        trace.end()
        assert tr.retained_tail == 1
        spans = tr.recent()
        assert [s["name"] for s in spans] == ["step.dispatch"]
        assert spans[0]["error"]

    def test_slow_trace_is_retained_fast_clean_dropped(self):
        tr = self._tracer()
        slow = tr.trace("plan")
        # already-measured stage span: 200ms >= the 50ms threshold
        slow.record("step.dispatch", 0.2)
        slow.end()
        fast = tr.trace("plan")
        with fast.span("step.dispatch"):
            pass
        fast.end()
        assert tr.retained_tail == 1
        assert tr.dropped_tail == 1
        assert len(tr.recent()) == 1

    def test_retained_trace_accepts_late_async_spans(self):
        """The dispatcher ends the trace at egress; outbound delivery
        spans finish AFTER end() on a worker thread — a retained trace
        must still collect them (sampled flips at decision time)."""
        tr = self._tracer()
        trace = tr.trace("plan")
        with pytest.raises(RuntimeError):
            with trace.span("step.dispatch"):
                raise RuntimeError("boom")
        trace.end()
        assert trace.sampled  # decision flipped the handle
        with trace.span("outbound.deliver"):
            pass
        names = {s["name"] for s in tr.recent()}
        assert names == {"step.dispatch", "outbound.deliver"}

    def test_dropped_trace_late_spans_never_repend(self):
        """The zombie-entry hazard: a DROPPED trace's async spans
        (outbound workers finish after the dispatcher's end()) must be
        discarded, not buffered into a fresh pending entry nobody will
        ever end — under load that would saturate the pending ring and
        evict genuinely in-flight traces early."""
        tr = self._tracer()
        trace = tr.trace("plan")
        with trace.span("step.dispatch"):
            pass
        trace.end()
        assert tr.dropped_tail == 1
        with trace.span("outbound.deliver"):   # late async leg
            pass
        assert len(tr._pending) == 0
        assert tr.recent() == []
        trace.end()   # idempotent: never double-counts
        assert tr.dropped_tail == 1

    def test_dropped_trace_late_error_span_reopens_retention(self):
        """The async blind spot: a connector failing AFTER the plan's
        drop decision must still surface — the late errored span
        re-opens retention (and subsequent spans of that trace land
        too), without re-opening the pending entry."""
        tr = self._tracer()
        trace = tr.trace("plan")
        with trace.span("step.dispatch"):
            pass
        trace.end()
        assert tr.dropped_tail == 1
        with pytest.raises(RuntimeError):
            with trace.span("outbound.deliver"):   # async leg fails
                raise RuntimeError("connector down")
        assert tr.retained_tail == 1
        assert tr.dropped_tail == 0
        assert len(tr._pending) == 0
        spans = tr.recent()
        assert [s["name"] for s in spans] == ["outbound.deliver"]
        assert spans[0]["error"]
        with trace.span("outbound.deliver"):   # retry leg: retained too
            pass
        assert len(tr.recent()) == 2

    def test_pending_eviction_still_decides(self):
        """An abandoned error trace (owner crashed before end()) is
        evicted when the pending buffer fills — and still retained."""
        tr = self._tracer(pending_capacity=4)
        victim = tr.trace("plan")
        with pytest.raises(RuntimeError):
            with victim.span("step.dispatch"):
                raise RuntimeError("abandoned")
        # never call victim.end(); now flood the pending buffer
        for _ in range(8):
            t = tr.trace("plan")
            with t.span("step.dispatch"):
                pass
        assert tr.retained_tail == 1
        assert "step.dispatch" in {s["name"] for s in tr.recent()}

    def test_head_and_tail_counters_are_seed_deterministic(self):
        def run():
            tr = Tracer(sample_rate=0.5, tail_errors=True, seed=1234)
            for i in range(64):
                t = tr.trace("plan")
                with t.span("s"):
                    pass
                t.end()
            return tr.sampled, tr.retained_tail, tr.dropped_tail
        assert run() == run()

    def test_tail_disabled_costs_nothing(self):
        tr = Tracer(sample_rate=0.0)
        t = tr.trace("plan")
        t.end()  # noop trace: end() is a no-op too
        assert tr.recent() == []
        assert len(tr._pending) == 0


# ---------------------------------------------------------------------------
# metric-name lint over a real instance (tier-1 contract)
# ---------------------------------------------------------------------------

def test_instance_metric_names_follow_dotted_convention(tmp_path):
    """Boot an instance, push events through the full pipeline (so the
    dispatcher/batcher/outbound instruments all register), then lint
    every name in the instance and process-global registries against
    the ``subsystem.noun_verb`` dotted convention."""
    import json

    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    cfg = Config({
        "instance": {"id": "lint-test", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 256,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    try:
        dm = inst.device_management
        dm.create_device_type(token="sensor", name="S")
        dm.create_device(token="d-0", device_type="sensor")
        dm.create_device_assignment(device="d-0")
        lines = [json.dumps({
            "deviceToken": "d-0", "type": "Measurement",
            "request": {"name": "t", "value": 1.0,
                        "eventDate": 1_753_800_000 + i}})
            for i in range(64)]
        inst.dispatcher.ingest_wire_lines("\n".join(lines).encode())
        inst.dispatcher.flush()
        inst.event_store.flush()

        names = inst.metrics.names() + global_registry().names()
        assert names, "no metrics registered — instrumentation unplugged?"
        bad = [n for n in names if not METRIC_NAME_RE.match(n)]
        assert not bad, f"metric names violate the dotted convention: {bad}"
        # family rules (closed memberships, governed prefixes) are
        # swlint's registry-driven metric-name pass — the dynamic lint
        # calls the SAME helper so runtime and static checks enforce
        # one contract (sitewhere_tpu/analysis/metric_names.py)
        from sitewhere_tpu.analysis.metric_names import lint_names

        problems = lint_names(names)
        assert not problems, f"metric family lint: {problems}"
        # the hot-path families the observability story promises
        assert "pipeline.e2e_latency_s" in names
        assert "pipeline.ingest_to_seal_latency_s" in names
        assert "ingest.batch_wait_s" in names
        # zero-copy ingest evidence family (ISSUE 10): per-stage bytes
        # copied + the native-build fallback gauge, lint-clean and
        # pre-registered so the exposition carries them from boot
        for name in ("pipeline.bytes_copied.decode",
                     "pipeline.bytes_copied.batch",
                     "pipeline.bytes_copied.h2d",
                     "native.build_fallbacks"):
            assert name in names, name
            assert METRIC_NAME_RE.match(name), name
    finally:
        inst.stop()
        inst.terminate()


def test_sanitize_is_idempotent_and_total():
    for raw in ("UPPER.Case", "a b.c:d", "tcp-receiver:9090.restarts",
                "weird/πath.x"):
        s = sanitize_metric_name(raw)
        assert sanitize_metric_name(s) == s
        assert not _has_invalid(s)


def _has_invalid(s):
    import re

    return re.search(r"[^a-z0-9_.-]", s) is not None

"""Resilience primitives: retry schedules, circuit breaker state machine,
supervised workers, and the unified dead-letter surface.

These are the building blocks every failure path in the pipeline now
shares (ingest reconnects, RPC channel backoff, outbound bulk retries,
command delivery, event-store seal retries) — so their semantics are
pinned here exactly: schedules, thresholds, transitions, and the metrics
each one ticks.
"""

import threading
import time

import pytest

from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.resilience import (
    Backoff,
    BreakerOpen,
    CircuitBreaker,
    CollectingSink,
    DeadLetterSink,
    RetriesExhausted,
    RetryPolicy,
    Supervisor,
    call_with_retry,
    dead_letter,
)

from time_limit import wait_until


# ---------------------------------------------------------------------------
# RetryPolicy / Backoff
# ---------------------------------------------------------------------------

class TestRetryPolicy:
    def test_exponential_schedule_capped(self):
        p = RetryPolicy(initial_s=0.1, max_s=1.0, factor=2.0)
        assert [p.delay(a) for a in range(6)] == pytest.approx(
            [0.1, 0.2, 0.4, 0.8, 1.0, 1.0])

    def test_jitter_is_bounded_and_seeded(self):
        import random

        p = RetryPolicy(initial_s=1.0, max_s=10.0, jitter=0.2)
        draws = [p.delay(0, random.Random(42)) for _ in range(20)]
        # same seed → same first draw (reproducible chaos schedules)
        assert draws[0] == p.delay(0, random.Random(42))
        for d in [p.delay(0, random.Random(s)) for s in range(50)]:
            assert 0.8 <= d <= 1.2

    def test_no_rng_means_no_jitter(self):
        p = RetryPolicy(initial_s=1.0, jitter=0.5)
        assert p.delay(0) == 1.0

    def test_exhausted_by_attempts(self):
        p = RetryPolicy(max_attempts=3)
        assert not p.exhausted(2)
        assert p.exhausted(3)

    def test_exhausted_by_deadline(self):
        p = RetryPolicy(deadline_s=5.0)
        assert not p.exhausted(100, started_at=0.0, now=4.9)
        assert p.exhausted(0, started_at=0.0, now=5.0)

    def test_unbounded_by_default(self):
        assert not RetryPolicy().exhausted(10_000)

    def test_huge_attempt_saturates_at_cap(self):
        # factor**attempt overflows float near attempt 1024; a cursor
        # that grew through a day-long outage must get max_s, not raise
        p = RetryPolicy(initial_s=0.1, max_s=30.0)
        assert p.delay(1_000_000) == 30.0


class TestBackoff:
    def test_delays_follow_policy_and_reset(self):
        b = Backoff(RetryPolicy(initial_s=0.1, max_s=1.0),
                    metrics=MetricsRegistry())
        assert [b.next_delay() for _ in range(3)] == pytest.approx(
            [0.1, 0.2, 0.4])
        b.reset()
        assert b.next_delay() == pytest.approx(0.1)

    def test_defer_and_due(self):
        b = Backoff(RetryPolicy(initial_s=10.0), metrics=MetricsRegistry())
        assert b.due(now=0.0)   # never deferred: always due
        b.defer(now=100.0)
        assert not b.due(now=105.0)
        assert b.remaining(now=105.0) == pytest.approx(5.0)
        assert b.due(now=110.0)

    def test_retries_tick_named_counter(self):
        reg = MetricsRegistry()
        b = Backoff(RetryPolicy(), name="unit.test", metrics=reg)
        b.next_delay()
        b.next_delay()
        assert reg.counter("resilience.retries.unit.test").value == 2

    def test_exhausted_tracks_policy(self):
        b = Backoff(RetryPolicy(max_attempts=2), metrics=MetricsRegistry())
        assert not b.exhausted()
        b.next_delay()
        b.next_delay()
        assert b.exhausted()


# ---------------------------------------------------------------------------
# call_with_retry
# ---------------------------------------------------------------------------

class TestCallWithRetry:
    def test_retries_then_succeeds(self):
        calls = {"n": 0}
        slept = []

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "ok"

        out = call_with_retry(
            flaky, RetryPolicy(initial_s=0.01, max_attempts=5),
            retry_on=(OSError,), sleep=slept.append,
            metrics=MetricsRegistry())
        assert out == "ok"
        assert calls["n"] == 3
        assert slept == pytest.approx([0.01, 0.02])

    def test_exhaustion_raises_with_cause(self):
        with pytest.raises(RetriesExhausted) as ei:
            call_with_retry(
                lambda: (_ for _ in ()).throw(OSError("down")),
                RetryPolicy(initial_s=0.0, max_attempts=2),
                retry_on=(OSError,), sleep=lambda s: None,
                metrics=MetricsRegistry())
        assert isinstance(ei.value.__cause__, OSError)

    def test_unlisted_exception_propagates_immediately(self):
        calls = {"n": 0}

        def bad():
            calls["n"] += 1
            raise ValueError("not transient")

        with pytest.raises(ValueError):
            call_with_retry(bad, RetryPolicy(max_attempts=5),
                            retry_on=(OSError,), sleep=lambda s: None,
                            metrics=MetricsRegistry())
        assert calls["n"] == 1

    def test_unbounded_policy_rejected(self):
        # call_with_retry blocks between attempts: an unbounded schedule
        # against a dead target would never return — Backoff loops own
        # unbounded schedules, not this call
        with pytest.raises(ValueError):
            call_with_retry(lambda: None, RetryPolicy(),
                            metrics=MetricsRegistry())

    def test_on_retry_hook_and_counter(self):
        reg = MetricsRegistry()
        seen = []
        with pytest.raises(RetriesExhausted):
            call_with_retry(
                lambda: (_ for _ in ()).throw(OSError("x")),
                RetryPolicy(initial_s=0.0, max_attempts=2),
                retry_on=(OSError,), name="unit.hook",
                on_retry=lambda a, e: seen.append((a, str(e))),
                sleep=lambda s: None, metrics=reg)
        assert seen == [(0, "x"), (1, "x")]
        assert reg.counter("resilience.retries.unit.hook").value == 2


# ---------------------------------------------------------------------------
# CircuitBreaker
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def make_breaker(**kw):
    clock = FakeClock()
    reg = MetricsRegistry()
    kw.setdefault("window", 8)
    kw.setdefault("failure_threshold", 0.5)
    kw.setdefault("min_calls", 4)
    kw.setdefault("open_for_s", 10.0)
    b = CircuitBreaker(name="unit", clock=clock, metrics=reg, **kw)
    return b, clock, reg


class TestCircuitBreaker:
    def test_stays_closed_below_min_calls(self):
        b, _, _ = make_breaker()
        for _ in range(3):
            b.record_failure()
        assert b.state == CircuitBreaker.CLOSED

    def test_trips_open_at_failure_rate(self):
        b, _, reg = make_breaker()
        for _ in range(2):
            b.record_success()
        for _ in range(2):
            b.record_failure()
        assert b.state == CircuitBreaker.OPEN
        assert reg.counter("resilience.breaker.unit.to_open").value == 1

    def test_open_sheds_instead_of_queueing(self):
        b, _, reg = make_breaker(min_calls=1, failure_threshold=1.0)
        b.record_failure()
        assert b.state == CircuitBreaker.OPEN
        assert not b.allow()
        assert not b.allow()
        assert b.shed == 2
        assert reg.counter("resilience.breaker.unit.shed").value == 2
        with pytest.raises(BreakerOpen):
            b.call(lambda: "never runs")

    def test_half_open_probe_then_close(self):
        b, clock, _ = make_breaker(min_calls=1, failure_threshold=1.0,
                                   half_open_probes=1)
        b.record_failure()
        clock.t = 10.0
        assert b.state == CircuitBreaker.HALF_OPEN
        assert b.allow()          # the single probe
        assert not b.allow()      # further traffic still shed
        b.record_success()
        assert b.state == CircuitBreaker.CLOSED
        assert b.allow()

    def test_half_open_failure_reopens(self):
        b, clock, _ = make_breaker(min_calls=1, failure_threshold=1.0)
        b.record_failure()
        clock.t = 10.0
        assert b.allow()
        b.record_failure()
        assert b.state == CircuitBreaker.OPEN
        assert not b.allow()
        # re-open restarts the full cool-down from the failure time
        clock.t = 19.9
        assert b.state == CircuitBreaker.OPEN

    def test_window_slides(self):
        # old failures age out: 4 failures then `window` successes must
        # not trip on one more failure
        b, _, _ = make_breaker(window=4, min_calls=4)
        for _ in range(4):
            b.record_failure()
        # tripping happened; reset by walking through half-open
        assert b.state == CircuitBreaker.OPEN

    def test_call_records_outcomes(self):
        b, _, _ = make_breaker(min_calls=2, failure_threshold=1.0)
        assert b.call(lambda: 7) == 7
        with pytest.raises(OSError):
            b.call(lambda: (_ for _ in ()).throw(OSError("x")))
        assert b.state == CircuitBreaker.CLOSED  # 1/2 failed < 1.0


# ---------------------------------------------------------------------------
# Supervisor (satellite: permanent failure must escalate, not spin)
# ---------------------------------------------------------------------------

class TestSupervisor:
    def test_transient_failures_restart_with_backoff(self):
        reg = MetricsRegistry()
        calls = {"n": 0}
        done = threading.Event()

        def worker():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError(f"crash {calls['n']}")
            done.set()   # third run stays healthy and exits cleanly

        sup = Supervisor("unit-rx", worker,
                         policy=RetryPolicy(initial_s=0.01, max_s=0.1),
                         max_restarts=8, min_uptime_s=60.0, metrics=reg)
        sup.start()
        assert done.wait(10.0)
        sup.stop()
        assert calls["n"] == 3
        assert sup.restarts == 2
        assert not sup.escalated
        # backoff actually escalates between consecutive restarts
        assert sup.restart_delays == pytest.approx([0.01, 0.02])
        assert reg.counter(
            "resilience.supervisor.unit-rx.restarts").value == 2

    def test_permanent_failure_escalates_terminally(self, caplog):
        """A receiver that fails permanently must stop after max_restarts
        with a terminal metric + log line — not spin forever."""
        reg = MetricsRegistry()
        calls = {"n": 0}
        escalations = []

        def dead_worker():
            calls["n"] += 1
            raise OSError("permanently down")

        sup = Supervisor("dead-rx", dead_worker,
                         policy=RetryPolicy(initial_s=0.001, max_s=0.01),
                         max_restarts=3, min_uptime_s=60.0,
                         on_escalate=escalations.append, metrics=reg)
        with caplog.at_level("ERROR", logger="sitewhere_tpu.resilience"):
            sup.start()
            assert wait_until(lambda: not sup.alive)
        assert sup.escalated
        assert calls["n"] == sup.max_restarts + 1  # initial run + restarts
        assert sup.restarts == sup.max_restarts
        assert reg.counter(
            "resilience.supervisor.dead-rx.escalated").value == 1
        assert len(escalations) == 1
        assert isinstance(escalations[0], OSError)
        assert any("giving up" in r.message and "terminal" in r.message
                   for r in caplog.records)
        # terminal means terminal: the count must not keep growing
        n = calls["n"]
        time.sleep(0.05)
        assert calls["n"] == n

    def test_clean_exit_never_restarts(self):
        calls = {"n": 0}

        def once():
            calls["n"] += 1

        sup = Supervisor("oneshot", once, metrics=MetricsRegistry())
        sup.start()
        assert wait_until(lambda: not sup.alive)
        assert calls["n"] == 1
        assert sup.restarts == 0

    def test_stop_interrupts_backoff(self):
        sup = Supervisor(
            "stoppable", lambda: (_ for _ in ()).throw(OSError("x")),
            policy=RetryPolicy(initial_s=60.0), max_restarts=8,
            metrics=MetricsRegistry())
        sup.start()
        assert wait_until(lambda: sup.restarts >= 1 or sup.last_error)
        t0 = time.monotonic()
        sup.stop()
        assert time.monotonic() - t0 < 10.0
        assert not sup.alive


# ---------------------------------------------------------------------------
# dead letters
# ---------------------------------------------------------------------------

class TestDeadLetter:
    def test_journal_satisfies_sink_protocol(self, tmp_path):
        from sitewhere_tpu.ingest.journal import Journal

        j = Journal(str(tmp_path), fsync_every=0)
        assert isinstance(j, DeadLetterSink)
        assert isinstance(CollectingSink(), DeadLetterSink)

    def test_dead_letter_counts_by_kind(self):
        reg = MetricsRegistry()
        sink = CollectingSink()
        assert dead_letter(sink, {"kind": "failed-decode"}, metrics=reg)
        assert dead_letter(sink, {"kind": "failed-decode"}, metrics=reg)
        assert dead_letter(sink, {"kind": "connector-shed"}, metrics=reg)
        assert len(sink) == 3
        snap = reg.snapshot()["counters"]
        assert snap["resilience.dead_letters"] == 3
        assert snap["resilience.dead_letters.failed-decode"] == 2
        assert snap["resilience.dead_letters.connector-shed"] == 1

    def test_missing_sink_still_counts(self):
        reg = MetricsRegistry()
        assert not dead_letter(None, {"kind": "x"}, metrics=reg)
        assert reg.counter("resilience.dead_letters").value == 1

    def test_broken_sink_never_raises_into_data_path(self):
        class Broken:
            def append_json(self, doc):
                raise OSError("disk full")

        reg = MetricsRegistry()
        assert not dead_letter(Broken(), {"kind": "x"}, metrics=reg)
        # the totals report records actually recorded — a failed sink
        # write must not claim one
        assert reg.counter("resilience.dead_letters").value == 0
        assert reg.counter(
            "resilience.dead_letters.sink_errors").value == 1

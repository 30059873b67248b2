"""One span primitive for the plan's life (tier-1, CPU).

``Timer.time(**tags)`` is the one instrument: the region lands in the
registry timer (always) and, while a ``jax.profiler`` session is open,
as a host-plane event of the timer's name on the profiler's clock.
These tests hold it to that, and hold the stage timers built on it to
the additive reading PERF.md gives them:

    batch wait → dispatch_wait → dispatch → inflight_wait →
    egress (device_wait + host)          [single-step plans]
    batch wait → ring_wait → ring_dispatch → inflight_wait → egress
                                         [ring plans]
"""

import contextlib
import glob
import json

import jax
import pytest

from sitewhere_tpu.pipeline.step import STEP_STAGES
from sitewhere_tpu.runtime.checkpoint import SAVE_PHASES
from sitewhere_tpu.runtime.metrics import METRIC_NAME_RE, MetricsRegistry

WIDTH = 64
NEW_TIMERS = (
    "pipeline.device_wait_s",
    "pipeline.stage_inflight_wait_s",
    "pipeline.stage_dispatch_wait_s",
    "ingest.journal_append_s",
    "checkpoint.save_s",
)


def _host_events(trace_dir, name):
    """(plane, stats) of every profiler event called ``name``."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(path)
    return [(plane.name, dict(ev.stats))
            for plane in profile.planes for line in plane.lines
            for ev in line.events if ev.name == name]


# ---------------------------------------------------------------------------
# (a) the primitive
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("session", [True, False],
                         ids=["in-profiler-session", "no-session"])
def test_timer_region_is_an_observation_and_a_profiler_span(tmp_path,
                                                            session):
    timer = MetricsRegistry().timer("x.y_s")
    assert timer.name == "x.y_s"
    if session:
        jax.profiler.start_trace(str(tmp_path))
    try:
        with timer.time(seq=7) as span:
            pass
    finally:
        if session:
            jax.profiler.stop_trace()
    assert timer.count == 1
    assert timer.total == span.elapsed >= 0.0
    if session:
        events = _host_events(tmp_path, "x.y_s")
        assert len(events) == 1
        plane, stats = events[0]
        assert plane.startswith("/host:") and stats["seq"] == 7


def test_timed_span_is_one_class_and_sanitised_names_reach_the_timer():
    reg = MetricsRegistry()
    timer = reg.timer("Ingest.Journal Append_s")
    assert timer.name == "ingest.journal_append_s"
    assert reg.timer("ingest.journal_append_s") is timer
    a, b = timer.time(), timer.time(seq=1)
    assert type(a) is type(b) and not hasattr(a, "__dict__")


def test_timed_span_observes_a_raising_region_and_discard_drops_one():
    timer = MetricsRegistry().timer("x.y_s")
    with pytest.raises(ValueError):
        with timer.time():
            raise ValueError("the time was spent all the same")
    assert timer.count == 1
    with timer.time() as span:
        span.discard()
    assert timer.count == 1 and span.elapsed >= 0.0


# ---------------------------------------------------------------------------
# (b) a plan's life adds up
# ---------------------------------------------------------------------------

def _instance(tmp_path, **pipeline):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    cfg = Config({
        "instance": {"id": "spans", "data_dir": str(tmp_path / "data")},
        "pipeline": dict({"width": WIDTH, "registry_capacity": 256,
                          "mtype_slots": 4, "deadline_ms": 5.0,
                          "n_shards": 1}, **pipeline),
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "checkpoint": {"interval_s": 0},
    }, apply_env=False)
    inst = Instance(cfg)
    inst.start()
    dm = inst.device_management
    dm.create_device_type(token="sensor", name="S")
    dm.create_device(token="d-0", device_type="sensor")
    dm.create_device_assignment(device="d-0")
    return inst


def _payload(i):
    return "\n".join(json.dumps({
        "deviceToken": "d-0", "type": "Measurement",
        "request": {"name": "t", "value": 1.0,
                    "eventDate": 1_753_800_000 + i * WIDTH + k}})
        for k in range(WIDTH)).encode()


@pytest.mark.parametrize("ring_depth", [0, 2], ids=["single-step", "ring-2"])
def test_stage_timers_add_up_to_the_plans_latency(tmp_path, ring_depth):
    """One full-width plan per payload, so no plan queues behind a
    sibling of its own intake: over the run, batch wait + the stage
    timers come to Σ latencies_s; what is left is the unattributed
    share (the few statements between one span's end and the next
    span's start)."""
    inst = _instance(tmp_path, ring_depth=ring_depth)
    try:
        d, reg = inst.dispatcher, inst.metrics

        def totals():
            snap = reg.snapshot()
            out = {s: reg.timer(f"pipeline.stage_{s}_s").total
                   for s in ("inflight_wait", "egress", "ring_wait",
                             "ring_dispatch", "dispatch_wait", "dispatch")}
            out["batch_wait"] = snap["histograms"][
                "pipeline.batch_assemble_s"]["sum"]
            return out

        # the first plans compile inside their dispatch span: take them
        # out, so the measured plans are steady-state ones
        warm, n = 2, 6
        for i in range(warm):
            d.ingest_wire_lines(_payload(i))
        d.flush()
        # a thread descheduled between two spans by the machine's other
        # load adds to the unattributed share and never takes from it:
        # every round holds the timers to at most 10% over the latency (a
        # span counted twice, or spans that overlap, is no noise), and up
        # to three rounds of n plans get to come within 10% under it
        for first in range(warm, warm + 3 * n, n):
            before = totals()
            for i in range(first, first + n):
                d.ingest_wire_lines(_payload(i))
            d.flush()
            parts = {k: v - before[k] for k, v in totals().items()}
            lat = list(d.latencies_s)[first:]
            assert len(lat) == n
            if ring_depth:
                # every slot of a chain waits out the whole chain's
                # dispatch ...
                parts["ring_dispatch"] *= ring_depth
                # ... and ring_wait covers what blocked its last plan
                del parts["dispatch_wait"]
                assert parts.pop("dispatch") == 0.0
            else:
                assert parts.pop("ring_wait") == parts.pop(
                    "ring_dispatch") == 0
            attributed, whole = sum(parts.values()), sum(lat)
            print(f"\nplans {n}  latency {whole * 1e3:.2f} ms  attributed "
                  f"{attributed * 1e3:.2f} ms  unattributed "
                  f"{(whole - attributed) / whole:+.2%}  parts "
                  + ", ".join(f"{k} {v * 1e3:.2f}"
                              for k, v in parts.items()))
            assert attributed <= whole * 1.10
            if attributed >= whole * 0.90:
                break
        else:
            pytest.fail("three rounds each left over 10% of the plans' "
                        "latency unattributed")
        plans = first + n
        assert reg.timer("pipeline.stage_egress_s").count == plans
        if ring_depth:
            assert reg.counter("pipeline.ring_chains").value == (
                plans // ring_depth)

        wait = reg.timer("pipeline.device_wait_s")
        assert wait.count == reg.counter("pipeline.host_syncs").value > 0
        assert wait.total <= reg.timer("pipeline.stage_egress_s").total
        assert reg.timer("pipeline.stage_inflight_wait_s").count == plans
        assert reg.timer("pipeline.stage_dispatch_wait_s").count >= (
            0 if ring_depth else plans)
        # per payload, the ingest journal only
        assert reg.timer("ingest.journal_append_s").count == plans
        assert inst.dead_letters._append_span is contextlib.nullcontext
    finally:
        inst.stop()


# ---------------------------------------------------------------------------
# (c) the checkpoint's phases, (e) the names
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """The registry of an instance after traffic and ONE save()."""
    inst = _instance(tmp_path_factory.mktemp("ckpt"))
    try:
        inst.dispatcher.ingest_wire_lines(_payload(0))
        inst.dispatcher.flush()
        assert inst.metrics.timer("checkpoint.save_s").count == 0
        inst.checkpointer.save()
        yield inst.metrics
    finally:
        inst.stop()


@pytest.mark.parametrize("phase", SAVE_PHASES)
def test_one_save_leaves_one_observation_per_phase(saved, phase):
    timer = saved.timer(f"checkpoint.phase_{phase}_s")
    assert timer.count == 1 and timer.total > 0.0


def test_checkpoint_phases_sum_to_the_save(saved):
    save = saved.timer("checkpoint.save_s")
    phases = sum(saved.timer(f"checkpoint.phase_{p}_s").total
                 for p in SAVE_PHASES)
    assert save.count == 1
    assert phases <= save.total
    assert phases == pytest.approx(save.total, rel=0.05)


@pytest.mark.parametrize("name", NEW_TIMERS)
def test_new_timers_are_registered_under_linted_names(saved, name):
    from sitewhere_tpu.analysis.metric_names import lint_names

    assert name in saved.names() and METRIC_NAME_RE.match(name)
    assert not lint_names([name])


# ---------------------------------------------------------------------------
# (d) names on the device step
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def step_text():
    from sitewhere_tpu.pipeline.step import pipeline_step
    from sitewhere_tpu.schema import DeviceState, RuleTable, ZoneTable
    from tests.helpers import make_batch, make_registry, measurement

    args = (make_registry(), DeviceState.empty(64, 4), RuleTable.empty(4),
            ZoneTable.empty(4), make_batch([measurement(0, value=1.0)]))
    return jax.jit(pipeline_step).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("stage", STEP_STAGES)
def test_device_step_stages_are_named_in_the_lowered_program(step_text,
                                                             stage):
    assert f"/{stage}/" in step_text

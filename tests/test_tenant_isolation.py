"""Multitenant isolation under load (ROADMAP item 5's fairness story).

Deterministic (fake-clock) coverage of the four isolation planes:

- **Budget overlays** (``runtime/overload.py`` TenantBudgets): the
  configured per-tenant DEGRADED ceiling COMPOSES with the ledger's
  measured-share scaling — effective rate = min of the two — so a
  configured budget can only tighten, a noisy tenant can never push a
  quiet one below its fairness floor, and stale buckets re-derive their
  rate within ``budget_refresh_s``.
- **Metered quotas** (``runtime/metering.py`` QuotaTable): windowed
  ``eval_s`` consumption walks the ok → deprioritized → refused ladder,
  429s are retryable because the refusal clears when the window
  rotates, and the ingest hot path never consults the table.
- **Partitioned state** (``state/manager.py`` TenantPartitions): pow2
  rung ladders with shrink-at-quarter hysteresis; one tenant's
  registration churn bumps only ITS ``compile_count``.
- **Budget dead-letters**: budget-bound sheds carry their own
  replayable kind ``tenant-budget`` and the requeue path re-checks the
  tenant's CURRENT budget.
"""

import json

import numpy as np
import pytest

from sitewhere_tpu.pipeline.packed import TENANT_METER_SLOTS
from sitewhere_tpu.runtime.metering import QuotaTable, UsageLedger
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.overload import (
    OverloadController,
    OverloadShed,
    OverloadState,
    PriorityClass,
    TenantBudgets,
    TokenBucket,
)
from sitewhere_tpu.services.common import QuotaExceeded
from sitewhere_tpu.state.manager import TenantPartitions, _next_pow2


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _charge_rows(led, ids):
    """Bill a device-block of accepted rows (the windowed-share path)."""
    block = np.zeros((3, TENANT_METER_SLOTS), np.int64)
    block[0] = np.bincount(np.asarray(ids) % TENANT_METER_SLOTS,
                           minlength=TENANT_METER_SLOTS)
    led.charge_device_block(block, np.asarray(ids, np.int32))


def _ledger(clock, **kw):
    kw.setdefault("fold_every", 1)
    kw.setdefault("fair_share_frac", 0.25)
    kw.setdefault("min_rate_frac", 0.1)
    return UsageLedger(clock=clock, **kw)


# ---------------------------------------------------------------------------
# fairness floor: the measured-share half of the composition
# ---------------------------------------------------------------------------

class TestFairnessFloor:
    @pytest.mark.parametrize("noisy_rows", [400, 4_000, 40_000])
    def test_noisy_volume_never_penalizes_quiet_tenant(self, noisy_rows):
        """Property: however loud the noisy tenant gets, a tenant at or
        under ``fair_share_frac`` keeps scale 1.0 — and the noisy one
        is floored at ``min_rate_frac``, never starved to zero."""
        clock = FakeClock()
        led = _ledger(clock)
        _charge_rows(led, np.full(noisy_rows, 1, np.int32))
        _charge_rows(led, np.full(100, 2, np.int32))
        assert led.shares()[2] <= led.fair_share_frac
        assert led.rate_scale(2) == 1.0
        assert led.min_rate_frac <= led.rate_scale(1) < 1.0

    def test_scale_tracks_share_then_floors(self):
        clock = FakeClock()
        led = _ledger(clock)
        _charge_rows(led, np.full(500, 1, np.int32))
        _charge_rows(led, np.full(500, 2, np.int32))
        # both at 2× fair share: both clipped to half the uniform budget
        assert led.rate_scale(1) == pytest.approx(0.5)
        assert led.rate_scale(2) == pytest.approx(0.5)
        # a monopolist's scale is floored at min_rate_frac, not zero
        # (fair/share can only undercut the floor when fair < floor)
        led2 = _ledger(clock, fair_share_frac=0.05, min_rate_frac=0.1)
        _charge_rows(led2, np.full(1_000, 1, np.int32))
        assert led2.shares()[1] == pytest.approx(1.0)
        assert led2.rate_scale(1) == pytest.approx(0.1)

    def test_topk_rotation_under_tenant_churn(self):
        """A churning long tail rotates through the top-K without
        losing mass: evicted tenants fold into ``other`` and totals
        stay conserved."""
        clock = FakeClock()
        led = _ledger(clock, top_k=4)
        total = 0
        for t in range(1, 33):         # 32 tenants through a K=4 sketch
            n = 10 + t
            _charge_rows(led, np.full(n, t, np.int32))
            total += n
        snap = led.snapshot()
        assert len(snap["tenants"]) <= 4
        tracked = sum(t["usage"]["rows"] for t in snap["tenants"])
        assert tracked + snap["other"]["rows"] == pytest.approx(total)
        assert snap["totals"]["rows"] == pytest.approx(total)
        # the heaviest recent tenants are the survivors
        survivors = {t["tenant_id"] for t in snap["tenants"]}
        assert 32 in survivors


# ---------------------------------------------------------------------------
# budget overlays: composition, attribution, refresh
# ---------------------------------------------------------------------------

def _controller(clock, **kw):
    kw.setdefault("metrics", MetricsRegistry())
    kw.setdefault("cooldown_s", 2.0)
    return OverloadController(clock=clock, **kw)


class TestBudgetComposition:
    def test_from_config_parses_overlay_sections(self):
        budgets = TenantBudgets.from_config({
            "t-a": {"overload": {"degraded_telemetry_rate_per_s": 50.0,
                                 "degraded_telemetry_burst": 10.0}},
            "t-b": {"quota": {"eval_s_per_window": 1.0}},   # no overload
            "t-c": "garbage",
        })
        assert budgets.get("t-a") == (50.0, 10.0)
        assert budgets.get("t-b") is None
        assert budgets.overlay("t-a") == {
            "degraded_telemetry_rate_per_s": 50.0,
            "degraded_telemetry_burst": 10.0}
        assert len(budgets) == 1

    def test_effective_rate_is_min_of_configured_and_measured(self):
        clock = FakeClock()
        led = _ledger(clock)
        _charge_rows(led, np.full(50, 1, np.int32))   # share 0.5 → ×0.5
        _charge_rows(led, np.full(50, 2, np.int32))
        c = _controller(clock, degraded_telemetry_rate_per_s=1000.0,
                        degraded_telemetry_burst=2000.0)
        c.set_usage_ledger(led, resolve={"noisy": 1, "quiet": 2}.get)
        # measured binds: configured 800 > measured 1000×0.5
        c.tenant_budgets.set_budget("noisy", rate_per_s=800.0)
        rate, burst, bound = c._telemetry_rate("noisy")
        assert rate == pytest.approx(500.0)
        assert not bound
        # configured binds: 200 < 500
        c.tenant_budgets.set_budget("noisy", rate_per_s=200.0, burst=100.0)
        rate, burst, bound = c._telemetry_rate("noisy")
        assert (rate, burst) == (pytest.approx(200.0), pytest.approx(100.0))
        assert bound

    def test_configured_overlay_only_ever_tightens(self):
        clock = FakeClock()
        c = _controller(clock, degraded_telemetry_rate_per_s=100.0,
                        degraded_telemetry_burst=50.0)
        # a generous overlay can never loosen the uniform budget
        c.tenant_budgets.set_budget("vip", rate_per_s=1e6, burst=1e6)
        rate, burst, bound = c._telemetry_rate("vip")
        assert (rate, burst) == (100.0, 50.0)
        assert not bound

    def test_admit_detail_attributes_budget_vs_overload(self):
        clock = FakeClock()
        c = _controller(clock, degraded_telemetry_rate_per_s=1000.0,
                        degraded_telemetry_burst=1000.0)
        c.tenant_budgets.set_budget("capped", rate_per_s=0.0, burst=2.0)
        c.force(OverloadState.DEGRADED)
        ok, reason = c.admit_detail(PriorityClass.TELEMETRY,
                                    tenant="capped", n=2)
        assert ok and reason == ""
        ok, reason = c.admit_detail(PriorityClass.TELEMETRY,
                                    tenant="capped", n=1)
        assert not ok and reason == "budget"
        assert c._metrics.counter("tenant.budget.clipped_rows").value == 1
        # a tenant WITHOUT an overlay refusing on the uniform bucket is
        # plain overload, not a budget clip
        c2 = _controller(clock, degraded_telemetry_rate_per_s=0.0,
                         degraded_telemetry_burst=1.0)
        c2.force(OverloadState.DEGRADED)
        assert c2.admit_detail(PriorityClass.TELEMETRY, tenant="t")[0]
        ok, reason = c2.admit_detail(PriorityClass.TELEMETRY, tenant="t")
        assert not ok and reason == "overload"

    def test_quiet_tenant_keeps_uniform_budget_while_noisy_clipped(self):
        """The fairness invariant end to end: DEGRADED admission clips
        the budgeted tenant while the quiet one rides the uniform
        bucket untouched."""
        clock = FakeClock()
        c = _controller(clock, degraded_telemetry_rate_per_s=0.0,
                        degraded_telemetry_burst=10.0)
        c.tenant_budgets.set_budget("noisy", rate_per_s=0.0, burst=2.0)
        c.force(OverloadState.DEGRADED)
        noisy_ok = sum(
            c.admit_detail(PriorityClass.TELEMETRY, tenant="noisy")[0]
            for _ in range(10))
        quiet_ok = sum(
            c.admit_detail(PriorityClass.TELEMETRY, tenant="quiet")[0]
            for _ in range(10))
        assert noisy_ok == 2          # clipped to the configured burst
        assert quiet_ok == 10         # full uniform burst

    def test_stale_bucket_reprices_within_refresh_interval(self):
        clock = FakeClock()
        c = _controller(clock, degraded_telemetry_rate_per_s=10.0,
                        degraded_telemetry_burst=100.0,
                        budget_refresh_s=5.0)
        c.tenant_budgets.set_budget("t", rate_per_s=0.0, burst=1.0)
        c.force(OverloadState.DEGRADED)
        assert c.admit(PriorityClass.TELEMETRY, tenant="t")
        assert not c.admit(PriorityClass.TELEMETRY, tenant="t")
        # operator loosens the budget mid-episode (still ≤ the uniform
        # ceiling — overlays only tighten): the already-built bucket
        # does NOT reprice until the refresh interval elapses...
        c.tenant_budgets.set_budget("t", rate_per_s=10.0, burst=50.0)
        assert not c.admit(PriorityClass.TELEMETRY, tenant="t")
        clock.t += 5.0
        # ...then reprices in place: 10/s over 5s accrued 50 tokens
        assert c.admit(PriorityClass.TELEMETRY, tenant="t", n=10)

    def test_set_rate_clamps_tokens_no_fresh_burst(self):
        clock = FakeClock()
        b = TokenBucket(rate_per_s=0.0, burst=100.0, clock=clock)
        assert b.try_take(40)                      # 60 tokens left
        b.set_rate(0.0, 10.0)                      # tightened: clamp to 10
        assert not b.try_take(11)
        assert b.try_take(10)
        # loosening never grants a fresh full burst mid-episode
        b2 = TokenBucket(rate_per_s=0.0, burst=5.0, clock=clock)
        assert b2.try_take(5)
        b2.set_rate(0.0, 1000.0)
        assert not b2.try_take(1)


# ---------------------------------------------------------------------------
# metered quotas: the ok → deprioritized → refused ladder
# ---------------------------------------------------------------------------

class TestQuotaLadder:
    def test_ladder_states_and_429(self):
        clock = FakeClock()
        led = _ledger(clock, window_s=60.0)
        quotas = QuotaTable(led, soft_frac=0.8, metrics=MetricsRegistry())
        quotas.set_quota(7, 1.0)
        assert quotas.state_of(7) == "ok"
        led.charge(7, "eval_s", 0.5)
        assert quotas.state_of(7) == "ok"
        led.charge(7, "eval_s", 0.35)             # 0.85 ≥ 0.8 × quota
        assert quotas.state_of(7) == "deprioritized"
        quotas.check_eval(7)                      # deprioritized ≠ refused
        led.charge(7, "eval_s", 0.2)              # 1.05 ≥ quota
        assert quotas.state_of(7) == "refused"
        with pytest.raises(QuotaExceeded) as exc:
            quotas.check_eval(7)
        assert exc.value.http_status == 429       # retryable, not a 403
        assert "retry" in str(exc.value)
        body = quotas.consumption(7)
        assert body["state"] == "refused"
        assert body["eval_s_remaining"] == 0.0
        # an unquota'd tenant is unlimited
        led.charge(9, "eval_s", 100.0)
        assert quotas.state_of(9) == "ok"
        assert quotas.consumption(9)["eval_s_quota"] is None

    def test_refusal_clears_when_window_rotates(self):
        clock = FakeClock()
        led = _ledger(clock, window_s=60.0, window_slices=12)
        quotas = QuotaTable(led)
        quotas.set_quota(3, 1.0)
        led.charge(3, "eval_s", 2.0)
        assert quotas.state_of(3) == "refused"
        clock.t += 61.0                           # window rotates off
        assert led.windowed_eval_s(3) == 0.0
        assert quotas.state_of(3) == "ok"
        quotas.check_eval(3)                      # no raise: retry worked

    def test_skip_mask_targets_only_throttled_tenants(self):
        clock = FakeClock()
        led = _ledger(clock)
        metrics = MetricsRegistry()
        quotas = QuotaTable(led, metrics=metrics)
        ids = np.array([1, 2, 1, 3, 2], np.int32)
        # fast path: no quota configured anywhere → None, zero work
        assert quotas.skip_mask(ids) is None
        quotas.set_quota(2, 1.0)
        assert quotas.skip_mask(ids) is None      # tenant 2 still ok
        led.charge(2, "eval_s", 5.0)
        mask = quotas.skip_mask(ids)
        assert mask.tolist() == [False, True, False, False, True]
        assert metrics.counter(
            "tenant.quota.eval_rows_skipped").value == 2

    def test_default_quota_applies_to_every_tenant(self):
        clock = FakeClock()
        led = _ledger(clock)
        quotas = QuotaTable(led, default_eval_s=0.5)
        led.charge(11, "eval_s", 0.6)
        assert quotas.state_of(11) == "refused"
        quotas.set_quota(11, 10.0)                # override loosens
        assert quotas.state_of(11) == "ok"


# ---------------------------------------------------------------------------
# partitioned device state: rung ladder, hysteresis, compile_count
# ---------------------------------------------------------------------------

class TestTenantPartitions:
    def _parts(self, column, min_capacity=4, metrics=None):
        return TenantPartitions(lambda: column, min_capacity=min_capacity,
                                metrics=metrics)

    def test_rung_ladder_grows_by_pow2(self):
        col = np.full(64, -1, np.int32)
        col[:3] = 1
        p = self._parts(col)
        p.refresh()
        assert p.partition_of(1) == {"count": 3, "rung": 4,
                                     "compile_count": 1}
        col[:9] = 1                               # 9 > rung 4 → grow
        p.refresh()
        assert p.partition_of(1)["rung"] == 16
        assert p.compile_count(1) == 2

    def test_shrink_only_at_quarter_occupancy(self):
        col = np.full(64, -1, np.int32)
        col[:32] = 5
        p = self._parts(col)
        p.refresh()
        assert p.partition_of(5)["rung"] == 32
        col[9:] = -1                              # 9 devices: > 32//4
        p.refresh()
        assert p.partition_of(5)["rung"] == 32    # hysteresis holds
        assert p.compile_count(5) == 1
        col[8:] = -1                              # 8 ≤ 32//4 → shrink
        p.refresh()
        assert p.partition_of(5)["rung"] == 8
        assert p.compile_count(5) == 2

    def test_untouched_tenant_compile_count_stays_flat_under_churn(self):
        """The churn-storm invariant: tenant 1's view never recompiles
        while tenant 2 registers and drops devices in waves."""
        col = np.full(256, -1, np.int32)
        col[:10] = 1
        metrics = MetricsRegistry()
        p = self._parts(col, metrics=metrics)
        p.refresh()
        baseline = p.compile_count(1)
        rng = np.random.default_rng(7)
        for _ in range(20):                       # churn waves: tenant 2
            col[10:] = -1
            n = int(rng.integers(1, 200))
            col[10:10 + n] = 2
            p.refresh()
        assert p.compile_count(1) == baseline == 1
        assert p.compile_count(2) > 1             # the churner DID resize
        assert metrics.gauge("tenant.partition.tracked").value == 2

    def test_padded_view_gathers_only_owned_rows(self):
        col = np.array([3, -1, 3, 9, 3, -1], np.int32)
        p = self._parts(col)
        p.refresh()
        idx, valid = p.indices_of(3)
        assert len(idx) == 4 and valid.sum() == 3
        state = {"x": np.arange(6) * 10.0}
        rows, vmask = p.view(state, 3)
        got = np.asarray(rows["x"])[np.asarray(vmask)]
        assert sorted(got.tolist()) == [0.0, 20.0, 40.0]
        assert p.view(state, 999) is None         # unknown tenant

    def test_gather_kernel_shared_per_rung(self):
        from sitewhere_tpu.state.manager import _partition_gather

        assert _partition_gather(16) is _partition_gather(16)
        assert _next_pow2(1) == 1 and _next_pow2(5) == 8
        assert _next_pow2(64) == 64


# ---------------------------------------------------------------------------
# tenant-budget dead-letters + replay re-checks the CURRENT budget
# ---------------------------------------------------------------------------

def _instance_config(tmp_path, tenants=None, overload=None):
    from sitewhere_tpu.runtime.config import Config

    return Config({
        "instance": {"id": "iso-inst", "data_dir": str(tmp_path / "data")},
        "pipeline": {"width": 64, "registry_capacity": 128,
                     "mtype_slots": 4, "deadline_ms": 5.0, "n_shards": 1},
        "presence": {"scan_interval_s": 3600.0, "missing_after_s": 1800},
        "overload": {"enabled": True, **(overload or {})},
        "tenants": tenants or {},
    }, apply_env=False)


def _measurement(token, value, ts=1_753_800_000):
    return json.dumps({
        "deviceToken": token, "type": "Measurement",
        "request": {"name": "temp", "value": value, "eventDate": ts},
    })


class TestTenantBudgetDeadLetter:
    def _decoded(self, inst, token, tenant, n):
        from sitewhere_tpu.ingest.decoders import JsonLinesDecoder

        payload = "\n".join(
            _measurement(token, float(i)) for i in range(n)).encode()
        reqs = JsonLinesDecoder()(payload)
        for r in reqs:
            r.metadata = dict(r.metadata or {}, tenant=tenant)
        return payload, reqs

    def test_budget_shed_kind_and_replay_recheck(self, tmp_path):
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(
            tmp_path,
            tenants={"t-noisy": {"overload": {
                "degraded_telemetry_rate_per_s": 0.0,
                "degraded_telemetry_burst": 0.0}}},
            # refresh every admit: budget changes reprice immediately.
            # The uniform DEGRADED bucket is out of the way (1e9/s): a
            # bucket refilled from empty grants by elapsed time, and at
            # the shipped 10,000/s the three replayed rows need 0.3 ms
            # between the refused replay and the granted one
            overload={"budget_refresh_s": 0.0,
                      "degraded_telemetry_rate_per_s": 1e9,
                      "degraded_telemetry_burst": 1e9}))
        inst.start()
        try:
            # each tenant owns the device its events name: a row that
            # claims another tenant than its device's owner is refused
            # and dead-lettered, never taken under the owner instead
            for tenant, token in (("t-quiet", "q-0"), ("t-noisy", "n-0")):
                inst.tenants.create_tenant(
                    token=tenant, name=tenant,
                    auth_token=f"{tenant}-auth-token-123")
                dm = inst.engines.get_engine(tenant).device_management
                dm.create_device_type(token="sensor", name="Sensor")
                dm.create_device(token=token, device_type="sensor")
                dm.create_device_assignment(device=token)
            inst.overload.force(OverloadState.DEGRADED)

            # quiet tenant sails through DEGRADED on the uniform bucket
            qp, qreqs = self._decoded(inst, "q-0", "t-quiet", 2)
            inst.dispatcher.ingest_many(qreqs, qp, "src-q")

            payload, reqs = self._decoded(inst, "n-0", "t-noisy", 3)
            with pytest.raises(OverloadShed):
                inst.dispatcher.ingest_many(reqs, payload, "src-n")
            letters = [d for d in inst.list_dead_letters(limit=50)
                       if d.get("kind") == "tenant-budget"]
            assert len(letters) == 1
            doc = letters[0]
            assert doc["tenant"] == "t-noisy"
            assert doc["reason"] == "tenant budget exceeded"
            assert doc["classes"] == {"telemetry": 3}
            assert doc["budget"] == {
                "degraded_telemetry_rate_per_s": 0.0,
                "degraded_telemetry_burst": 0.0}
            # distinct kind: nothing landed under the generic intake-shed
            assert not [d for d in inst.list_dead_letters(limit=50)
                        if d.get("kind") == "intake-shed"]

            # replay while STILL over budget: refused, record retryable
            refused = inst.requeue_dead_letter(doc["offset"])
            assert refused["requeued"] is False
            assert refused["reason"].startswith("still over tenant budget")

            # operator raises the budget: the SAME record replays, the
            # composed admission re-checking the CURRENT budget
            inst.overload.tenant_budgets.set_budget(
                "t-noisy", rate_per_s=1e6, burst=1e6)
            result = inst.requeue_dead_letter(doc["offset"])
            assert result["requeued"] is True and result["rows"] == 3
            inst.dispatcher.flush()
            inst.event_store.flush()
            assert inst.event_store.total_events == 5   # 2 quiet + 3 replay
            # the original shed AND the refused replay attempt both
            # count as budget clips (3 rows each)
            clipped = inst.metrics.counter(
                "tenant.budget.clipped_rows").value
            assert clipped == 6
        finally:
            inst.stop()
            inst.terminate()

    def test_usage_drilldown_carries_budget_and_quota(self, tmp_path):
        """Satellite: GET /api/tenants/usage/{token} explains WHY a
        tenant is throttled — live rate_scale + configured budget +
        quota consumption in one body."""
        from sitewhere_tpu.instance import Instance

        inst = Instance(_instance_config(
            tmp_path,
            tenants={"t-metered": {
                "overload": {"degraded_telemetry_rate_per_s": 123.0},
                "quota": {"eval_s_per_window": 2.0}}}))
        inst.start()
        try:
            from sitewhere_tpu.web.controllers import register_routes
            from sitewhere_tpu.web.http import RestGateway

            inst.tenants.create_tenant(token="t-metered", name="Metered")
            tid = inst.identity.tenant.lookup("t-metered")
            inst.usage_ledger.charge(int(tid), "eval_s", 1.9)

            gw = RestGateway()
            register_routes(gw, inst)
            handler, params, _, _ = gw.router.route(
                "GET", "/api/tenants/usage/t-metered")

            class _Q:
                def __init__(self, p):
                    self.params = p

                def q1(self, k, default=None):
                    return default

            body = handler(_Q(params))
            assert body["budget"] == {
                "degraded_telemetry_rate_per_s": 123.0}
            assert body["quota"]["eval_s_quota"] == 2.0
            assert body["quota"]["state"] == "deprioritized"
            assert body["quota"]["eval_s_remaining"] == pytest.approx(
                0.1, abs=1e-6)
            assert body["rate_scale"] == 1.0
        finally:
            inst.stop()
            inst.terminate()

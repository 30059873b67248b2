"""Bring-your-own-rules subsystem (sitewhere_tpu/rules).

The three contracts the issue pins:

1. **Bucketing** — arbitrary program populations collapse into at most
   ``MAX_STRUCTURE_KEYS`` compiled shapes, by construction.
2. **Golden equivalence** — the compiled group kernels agree with the
   numpy reference interpreter bit-for-bit on fired alerts and
   enrichment, over multi-batch streams with trailing state, including
   the mesh-sharded prepare path.
3. **Hot swap** — republishing a tenant's constants under traffic mints
   ZERO new kernel executables, in-flight batches finish on the epoch
   they grabbed, and the registry round-trips through a checkpoint.
"""

import json
import time

import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.rules import compile as rcompile
from sitewhere_tpu.rules.dsl import (
    MAX_STRUCTURE_KEYS,
    RuleProgramError,
    parse_program,
)
from sitewhere_tpu.rules.engine import RuleEngineRunner
from sitewhere_tpu.rules.enrich import AttributeStore
from sitewhere_tpu.rules.interp import (
    InterpTrail,
    interp_eval,
    interp_features,
)
from sitewhere_tpu.rules.registry import ProgramRegistry
from sitewhere_tpu.schema import DEFAULT_EWMA_TAUS, EventType

POLY = [[0.0, 0.0], [10.0, 0.0], [10.0, 10.0], [0.0, 10.0]]


def doc_value(token="r-value", thr=30.0, op="gt", level="warning"):
    return {"token": token, "alert": {"type": "byo.hot", "level": level},
            "when": {"pred": "value", "op": op, "value": thr}}


def doc_multi(token="r-multi", thr=50.0):
    return {"token": token, "alert": {"type": "byo.trend",
                                      "level": "error"},
            "when": {"any": [
                {"all": [{"pred": "ewma", "op": "gt", "value": thr,
                          "window_s": 600.0},
                         {"pred": "rate", "op": "gt", "value": 0.5}]},
                {"pred": "value", "op": "gt", "value": thr + 40.0}]}}


def doc_geo(token="r-geo", inside=True):
    return {"token": token, "alert": {"type": "byo.zone",
                                      "level": "critical"},
            "when": {"pred": "geo", "polygon": POLY, "inside": inside}}


def doc_attr(token="r-attr", tier=2):
    return {"token": token, "alert": {"type": "byo.tier",
                                      "level": "info"},
            "when": {"all": [
                {"pred": "value", "op": "gt", "value": 10.0},
                {"pred": "attr", "table": "device", "column": "tier",
                 "op": "eq", "value": tier}]}}


def make_batch(rng, n, n_devices, n_tenants, t0=1000, loc_frac=0.3):
    et = np.where(rng.random(n) < (1.0 - loc_frac),
                  int(EventType.MEASUREMENT),
                  int(EventType.LOCATION)).astype(np.int32)
    return {
        "device_id": rng.integers(0, n_devices, n).astype(np.int32),
        "tenant_id": rng.integers(0, n_tenants, n).astype(np.int32),
        "event_type": et,
        "mtype_id": rng.integers(0, 4, n).astype(np.int32),
        "value": rng.uniform(0.0, 100.0, n).astype(np.float32),
        "lon": rng.uniform(-5.0, 15.0, n).astype(np.float32),
        "lat": rng.uniform(-5.0, 15.0, n).astype(np.float32),
        "ts_s": (t0 + rng.integers(0, 500, n)).astype(np.int32),
        "ts_ns": rng.integers(0, 1_000_000, n).astype(np.int32),
        "asset_id": rng.integers(-1, 8, n).astype(np.int32),
    }


def collect_engine_alerts(eng):
    fired = []
    eng.inject = lambda cols: fired.extend(
        (int(cols["device_id"][i]), int(cols["ts_s"][i]),
         int(cols["alert_code"][i]), int(cols["alert_level"][i]))
        for i in range(len(cols["device_id"])))
    return fired


def interp_programs(registry):
    return [(t, p.canonical, p.alert_code)
            for g in registry._groups.values()
            for (t, _tok), p in sorted(g.programs.items())]


class TestDsl:
    def test_validation_rejects_malformed_docs(self):
        bad = [
            {},                                        # no token
            {"token": "x"},                            # no alert
            {"token": "x", "alert": {"type": "a"}},    # no when
            {"token": "x", "alert": {"type": "a"},
             "when": {"pred": "value", "op": "??", "value": 1}},
            {"token": "x", "alert": {"type": "a"},
             "when": {"pred": "value", "op": "gt"}},   # no threshold
            {"token": "x", "alert": {"type": "a"},
             "when": {"pred": "geo", "polygon": [[0, 0], [1, 1]]}},
            {"token": "x", "alert": {"type": "a", "level": "loud"},
             "when": {"pred": "value", "op": "gt", "value": 1}},
            {"token": "x", "alert": {"type": "a"},
             "when": {"any": [{"any": [{"pred": "value", "op": "gt",
                                        "value": 1}]}]}},  # nested any
            {"token": "x", "alert": {"type": "a"},
             "when": {"pred": "event_type", "value": "alert"}},  # loop
        ]
        for doc in bad:
            with pytest.raises(RuleProgramError):
                parse_program(doc)

    def test_spelling_order_shares_structure_and_canonical_form(self):
        a = {"token": "a", "alert": {"type": "t"},
             "when": {"all": [{"pred": "value", "op": "gt", "value": 5.0},
                              {"pred": "rate", "op": "lt", "value": 1.0}]}}
        b = {"token": "b", "alert": {"type": "t"},
             "when": {"all": [{"pred": "rate", "op": "lt", "value": 1.0},
                              {"pred": "value", "op": "gt", "value": 5.0}]}}
        pa, pb = parse_program(a), parse_program(b)
        assert pa.structure_key() == pb.structure_key()
        assert pa.clauses == pb.clauses

    def test_constants_never_change_the_structure_key(self):
        keys = {parse_program(doc_value(thr=t, op=o)).structure_key()
                for t in (1.0, 50.0, 99.0)
                for o in ("gt", "lt", "gte", "lte", "eq", "neq")}
        assert len(keys) == 1

    def test_bucketing_bound_holds_by_construction(self):
        # every legal (clauses, preds, geo) combination lands on a rung
        rng = np.random.default_rng(5)
        keys = set()
        for _ in range(200):
            n_cl = int(rng.integers(1, 5))
            clauses = []
            for _c in range(n_cl):
                n_p = int(rng.integers(1, 9))
                preds = [{"pred": "value", "op": "gt",
                          "value": float(rng.uniform(0, 99))}
                         for _ in range(n_p)]
                if rng.random() < 0.3:
                    preds[0] = {"pred": "geo", "polygon": POLY}
                clauses.append({"all": preds})
            doc = {"token": "x", "alert": {"type": "t"},
                   "when": {"any": clauses}}
            keys.add(parse_program(doc).structure_key())
        assert len(keys) <= MAX_STRUCTURE_KEYS


class TestGoldenEquivalence:
    D, M, T = 64, 4, 8

    def _engine(self):
        eng = RuleEngineRunner(capacity=self.D, n_mtype_slots=self.M,
                               asset_capacity=16, queue_depth=4)
        eng.registry.put_program(1, doc_value(thr=40.0))
        eng.registry.put_program(1, doc_multi())
        eng.registry.put_program(2, doc_geo())
        eng.registry.put_program(3, doc_geo("r-out", inside=False))
        eng.registry.put_program(3, doc_attr())
        eng.registry.put_program(5, doc_value("r-low", thr=20.0,
                                              op="lt", level="info"))
        eng.attributes.set("device", 7, "tier", 2)
        eng.attributes.set("device", 9, "tier", 1)
        eng.attributes.set("asset", 3, "grade", 4)
        eng.refresh()
        return eng

    def _interp_alerts(self, eng, batches):
        trail = InterpTrail(self.D, self.M, len(DEFAULT_EWMA_TAUS))
        cols_map, arrays = eng.attributes.snapshot_payload()
        progs = interp_programs(eng.registry)
        out = []
        for batch in batches:
            feats = interp_features(trail, batch, DEFAULT_EWMA_TAUS,
                                    arrays["device"], arrays["asset"])
            for row, _tok, code, lvl in interp_eval(progs, batch, feats):
                out.append((int(batch["device_id"][row]),
                            int(batch["ts_s"][row]), code, lvl))
        return sorted(out)

    def test_compiled_matches_interp_over_multibatch_stream(self):
        eng = self._engine()
        fired = collect_engine_alerts(eng)
        rng = np.random.default_rng(42)
        batches = [make_batch(rng, 96, self.D, self.T,
                              t0=1000 + 600 * i) for i in range(5)]
        for b in batches:
            eng._eval_batch(dict(b))
        assert sorted(fired) == self._interp_alerts(eng, batches)
        assert len(fired) > 0  # the stream must actually exercise rules

    def test_alert_rows_are_never_evaluated(self):
        eng = self._engine()
        fired = collect_engine_alerts(eng)
        rng = np.random.default_rng(0)
        batch = make_batch(rng, 64, self.D, self.T)
        batch["event_type"][:] = int(EventType.ALERT)
        eng._eval_batch(dict(batch))
        assert fired == []

    def test_mesh_dryrun_matches_interp(self):
        """Golden equivalence on the 4-shard CPU mesh: the sharded
        prepare (trail + device attrs sharded, features psummed) must
        produce the same alerts as the reference interpreter."""
        import jax

        from sitewhere_tpu.parallel import make_mesh

        if len(jax.devices()) < 4:
            pytest.skip("needs >= 4 XLA devices")
        mesh = make_mesh(4, devices=jax.devices()[:4])
        eng = RuleEngineRunner(capacity=self.D, n_mtype_slots=self.M,
                               asset_capacity=16, queue_depth=4,
                               mesh=mesh, rows_per_shard=self.D // 4)
        eng.registry.put_program(1, doc_value(thr=40.0))
        eng.registry.put_program(1, doc_multi())
        eng.registry.put_program(2, doc_geo())
        eng.attributes.set("device", 7, "tier", 2)
        eng.refresh()
        fired = collect_engine_alerts(eng)
        rng = np.random.default_rng(9)
        batches = [make_batch(rng, 64, self.D, self.T,
                              t0=1000 + 600 * i) for i in range(3)]
        for b in batches:
            eng._eval_batch(dict(b))
        ref = TestGoldenEquivalence._interp_alerts(self, eng, batches)
        assert sorted(fired) == ref
        assert len(fired) > 0

    def test_enrichment_join_semantics(self):
        """Attr predicates join the published tables; unset (NULL_ID)
        attributes never match, on either lane."""
        eng = RuleEngineRunner(capacity=16, n_mtype_slots=2,
                               asset_capacity=8, queue_depth=4)
        eng.registry.put_program(0, doc_attr(tier=2))
        eng.attributes.set("device", 3, "tier", 2)  # matches
        eng.attributes.set("device", 4, "tier", 1)  # wrong tier
        eng.refresh()                               # device 5: unset
        fired = collect_engine_alerts(eng)
        n = 3
        batch = {
            "device_id": np.asarray([3, 4, 5], np.int32),
            "tenant_id": np.zeros(n, np.int32),
            "event_type": np.full(n, int(EventType.MEASUREMENT), np.int32),
            "mtype_id": np.zeros(n, np.int32),
            "value": np.full(n, 50.0, np.float32),
            "lon": np.zeros(n, np.float32),
            "lat": np.zeros(n, np.float32),
            "ts_s": np.asarray([10, 10, 10], np.int32),
            "ts_ns": np.zeros(n, np.int32),
            "asset_id": np.full(n, NULL_ID, np.int32),
        }
        eng._eval_batch(dict(batch))
        assert [f[0] for f in fired] == [3]


class TestHotSwap:
    def _engine(self, n_tenants=8):
        eng = RuleEngineRunner(capacity=32, n_mtype_slots=2,
                               queue_depth=8)
        for t in range(n_tenants):
            eng.registry.put_program(
                t, doc_value(f"r{t}", thr=30.0 + t))
        eng.refresh()
        return eng

    def test_operand_swap_mints_no_new_executables(self):
        eng = self._engine()
        rng = np.random.default_rng(1)
        batch = make_batch(rng, 64, 32, 8)
        eng._eval_batch(dict(batch))  # warm the batch width
        before = rcompile.compile_count()
        for i in range(5):
            # swap constants on a live program, then evaluate under the
            # new epoch — the zero-stall contract
            eng.put_program(3, doc_value("r3", thr=10.0 + i, op="lt"))
            eng._eval_batch(dict(batch))
        assert rcompile.compile_count() == before
        assert eng.registry.swaps >= 5

    def test_swap_under_live_traffic_has_no_compile_stall(self):
        """Worker-threaded version: batches stream through submit_live
        while a swap lands; the post-swap eval latency must stay at
        batch scale (no seconds-long XLA compile on the eval path)."""
        eng = self._engine()
        eng.start()
        try:
            fired = collect_engine_alerts(eng)
            rng = np.random.default_rng(2)
            cols = make_batch(rng, 64, 32, 8)
            mask = np.ones(64, bool)
            eng.submit_live(cols, mask)
            eng.drain()
            before = rcompile.compile_count()
            steady = []
            for i in range(6):
                if i == 3:
                    eng.put_program(2, doc_value("r2", thr=5.0))
                t0 = time.perf_counter()
                eng.submit_live(cols, mask)
                eng.drain()
                steady.append(time.perf_counter() - t0)
            assert rcompile.compile_count() == before
            # post-swap batches stay at batch scale: no eval waited on
            # a fresh XLA compile (compiles are O(seconds))
            assert max(steady[3:]) < 2.0
            assert len(fired) > 0
        finally:
            eng.stop()

    def test_epoch_isolation_in_flight_plans_finish_on_old_epoch(self):
        eng = self._engine()
        epoch_a = eng.registry.current_epoch()
        eng.put_program(0, doc_value("r0", thr=99.0))
        epoch_b = eng.registry.current_epoch()
        assert epoch_b.epoch > epoch_a.epoch
        # the old epoch's tables are immutable — a batch that grabbed
        # epoch_a still evaluates the OLD threshold
        (g_a,) = [g for g in epoch_a.groups]
        (g_b,) = [g for g in epoch_b.groups]
        assert float(np.asarray(g_a.tables.pf).max()) != \
            float(np.asarray(g_b.tables.pf).max())
        # same shapes, same kernel: the swap could not have re-traced
        assert g_a.shape_sig() == g_b.shape_sig()
        assert g_a.eval_fn is g_b.eval_fn

    def test_checkpoint_round_trip_restores_programs_and_attrs(self):
        eng = self._engine()
        eng.attributes.set("device", 3, "tier", 7)
        eng.refresh()
        payload, header = eng.snapshot_state()
        eng2 = RuleEngineRunner(capacity=32, n_mtype_slots=2,
                                queue_depth=8)
        eng2.restore_state(header, payload)
        assert eng2.registry.program_count() == \
            eng.registry.program_count()
        assert eng2.registry.structure_keys() == \
            eng.registry.structure_keys()
        assert eng2.attributes.columns("device") == {"tier": 0}
        cols_map, arrays = eng2.attributes.snapshot_payload()
        assert arrays["device"][3, 0] == 7
        # restored engine fires identically on the same batch
        f1, f2 = collect_engine_alerts(eng), collect_engine_alerts(eng2)
        rng = np.random.default_rng(3)
        batch = make_batch(rng, 48, 32, 8)
        eng._eval_batch(dict(batch))
        eng2._eval_batch(dict(batch))
        assert sorted(f1) == sorted(f2)

    def test_structure_change_moves_program_between_groups(self):
        reg = ProgramRegistry()
        reg.put_program(0, doc_value("r0"))
        assert reg.structure_keys() == ["c2p4"]
        reg.put_program(0, doc_geo("r0"))  # same token, new structure
        assert reg.structure_keys() == ["c2p4g"]
        assert reg.program_count() == 1


class TestRegistryLimits:
    def test_per_tenant_structure_slots_enforced(self):
        reg = ProgramRegistry(programs_per_tenant=2)
        reg.put_program(0, doc_value("a"))
        reg.put_program(0, doc_value("b"))
        with pytest.raises(RuleProgramError):
            reg.put_program(0, doc_value("c"))
        # replacing in place is always allowed
        reg.put_program(0, doc_value("b", thr=99.0))

    def test_bad_doc_never_dirties_a_group(self):
        reg = ProgramRegistry()
        reg.put_program(0, doc_value("a"))
        reg.publish()
        with pytest.raises(RuleProgramError):
            reg.put_program(0, {"token": "b", "alert": {"type": "t"},
                                "when": {"pred": "value", "op": "gt"}})
        assert reg.publish().epoch == 1  # no rebuild happened

    def test_attribute_store_column_limit(self):
        store = AttributeStore(16, 8, max_columns=2)
        store.resolve("device", "a")
        store.resolve("device", "b")
        with pytest.raises(RuleProgramError):
            store.resolve("device", "c")


class TestRuleMetrics:
    def test_rules_family_is_registered_and_lint_clean(self):
        from sitewhere_tpu.analysis.metric_names import lint_names

        eng = RuleEngineRunner(capacity=16, queue_depth=2)
        assert lint_names(eng.metrics.names()) == []

    def test_engine_publishes_compiled_shape_gauges(self):
        eng = RuleEngineRunner(capacity=16, queue_depth=2)
        eng.registry.put_program(0, doc_value())
        eng.refresh()
        snap = {n: eng.metrics.gauge(n).value
                for n in ("rules.programs", "rules.compiled_shapes")}
        assert snap["rules.programs"] == 1
        assert snap["rules.compiled_shapes"] >= 1


class TestRulebenchSmoke:
    def test_tool_reports_bucketing_and_swap_stability(self):
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "tools", "rulebench.py")
        spec = importlib.util.spec_from_file_location("rulebench", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        result = mod.run(n_programs=256, n_tenants=32, n_devices=128,
                         n_events=4096, batch=1024, swap_every=1)
        assert result["programs_loaded"] > 0
        assert result["shapes_within_bound"]
        assert result["compiled_shapes"] <= result["max_structure_keys"]
        assert result["eval_events_per_s"] > 0
        assert result["builtin_events_per_s"] > 0
        # the acceptance bar: operand swaps under traffic never compile
        assert result["swaps_applied"] >= 1
        assert result["recompiles_during_swaps"] == 0
        table = mod._render(result)
        assert "compiled shapes" in table


def test_attribute_snapshot_holds_only_the_minted_columns():
    """Columns are minted densely and nothing else is written, so a
    store with no columns saves none of its capacity-sized table, and a
    restore over a store that held values leaves no stale one behind."""
    from sitewhere_tpu.ids import NULL_ID
    from sitewhere_tpu.rules.enrich import MAX_ATTR_COLUMNS, AttributeStore

    src = AttributeStore(device_capacity=64)
    cols, arrays = src.snapshot_payload()
    assert arrays["device"].shape == (64, 0)
    assert arrays["asset"].shape[1] == 0
    src.set("device", 5, "tier", 3)
    src.set("device", 6, "zone", 9)
    cols, arrays = src.snapshot_payload()
    assert arrays["device"].shape == (64, 2)
    assert arrays["asset"].shape[1] == 0

    dst = AttributeStore(device_capacity=64)
    dst.set("device", 7, "old", 1)
    dst.set("asset", 2, "grade", 4)
    dst.restore_payload(cols, arrays)
    assert dst.columns("device") == {"tier": 0, "zone": 1}
    assert dst.columns("asset") == {}
    host = dst._host["device"]
    assert host.shape == (64, MAX_ATTR_COLUMNS)
    assert host[5, 0] == 3 and host[6, 1] == 9
    assert host[7, 0] == NULL_ID and (dst._host["asset"] == NULL_ID).all()

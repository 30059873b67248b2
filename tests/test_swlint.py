"""swlint: the project-invariant static-analysis suite (tier-1 gate).

Covers:

- the golden fixture corpus: >=3 true-positive and >=3 true-negative
  snippets per pass under ``tests/fixtures/swlint/`` — a pass that
  stops firing on its TPs (or starts firing on its TNs) fails here;
- the REPO GATE: ``run_suite`` over ``sitewhere_tpu/`` must be clean —
  zero findings not suppressed by ``tools/swlint_baseline.json``, and
  every baseline entry must carry a real justification;
- the CLI (``tools/swlint.py``): exit codes, --json shape, --baseline,
  --update-baseline round-trip;
- fingerprint stability: a baseline survives the code moving to
  different line numbers;
- regressions for the two findings this suite surfaced and FIXED:
  the DeviceStateManager queries that held the lease lock through a
  blocking D2H, and the batcher ``_emit`` that paid 16 H2D transfers
  under the dispatcher intake lock.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from sitewhere_tpu.analysis import (
    Baseline,
    check_clean,
    default_baseline_path,
    hot_path,
    is_hot_path,
    run_suite,
)
from sitewhere_tpu.analysis.core import Finding, Project
from sitewhere_tpu.analysis.donation import DonationPass
from sitewhere_tpu.analysis.hotpath import HotPathAllocationPass
from sitewhere_tpu.analysis.locks import LockDisciplinePass
from sitewhere_tpu.analysis.metric_names import MetricNamePass, lint_names
from sitewhere_tpu.analysis.trace_purity import TracePurityPass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "sitewhere_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "swlint")
CLI = os.path.join(REPO, "tools", "swlint.py")


def _fixture_pass(passdir):
    """Pass instance tuned for the fixture corpus (fixture modules have
    their own class/lock names, so the repo-default config is widened
    where it is name-anchored)."""
    if passdir == "trace_purity":
        return TracePurityPass(dispatch_modules={"dispatch_path"})
    if passdir == "locks":
        return LockDisciplinePass(
            hot_locks=["Hot._lock", "Mgr._lock", "Pair._a", "Pair._b"],
            contracts={"Contracted.run_under_intake":
                       "fixture intake lock"},
            device_state_classes=["Mgr"])
    if passdir == "donation":
        return DonationPass()
    if passdir == "hotpath":
        return HotPathAllocationPass()
    return MetricNamePass()


# rule each true-positive fixture must fire (at least once)
EXPECTED_RULES = {
    ("trace_purity", "tp_item_in_jit.py"): "TP001",
    ("trace_purity", "tp_np_in_fori_body.py"): "TP001",
    ("trace_purity", "tp_print_in_shard_map.py"): "TP001",
    ("trace_purity", "tp_coerce_traced.py"): "TP002",
    ("trace_purity", "tp_dispatch_path.py"): "TP003",
    ("locks", "tp_inversion.py"): "LK001",
    ("locks", "tp_self_deadlock.py"): "LK002",
    ("locks", "tp_blocking_hot.py"): "LK003",
    ("locks", "tp_d2h_hot.py"): "LK004",
    ("locks", "tp_contract.py"): "LK003",
    ("locks", "tp_checkpoint_hot.py"): "LK005",
    ("donation", "tp_use_after_jit_donate.py"): "DN001",
    ("donation", "tp_use_after_chain.py"): "DN001",
    ("donation", "tp_retry_with_donated.py"): "DN001",
    ("donation", "tp_epoch_after_single_step.py"): "DN001",
    ("donation", "tp_use_after_lease.py"): "DN002",
    ("donation", "tp_use_after_commit.py"): "DN003",
    ("donation", "tp_use_after_abort.py"): "DN003",
    ("hotpath", "tp_list_in_hot.py"): "HP001",
    ("hotpath", "tp_ndarray_in_hot.py"): "HP002",
    ("hotpath", "tp_fstring_in_hot.py"): "HP003",
    ("hotpath", "tp_closure_in_hot.py"): "HP004",
    ("hotpath", "tp_propagated_callee.py"): "HP001",
    ("metric_names", "tp_malformed.py"): "MN001",
    ("metric_names", "tp_unknown_member.py"): "MN002",
    ("metric_names", "tp_typo_flightrec.py"): "MN002",
    ("metric_names", "tp_unregistered_family.py"): "MN003",
}

PASS_DIRS = sorted({d for d, _ in EXPECTED_RULES})


def _run_fixture(passdir, filename):
    path = os.path.join(FIXTURES, passdir, filename)
    project = Project.from_paths([path], root=os.path.dirname(path))
    return _fixture_pass(passdir).run(project)


def _fixture_files(passdir, prefix):
    d = os.path.join(FIXTURES, passdir)
    return sorted(f for f in os.listdir(d)
                  if f.startswith(prefix) and f.endswith(".py"))


# ---------------------------------------------------------------------------
# fixture corpus
# ---------------------------------------------------------------------------


class TestFixtureCorpus:
    @pytest.mark.parametrize("passdir", PASS_DIRS)
    def test_corpus_is_big_enough(self, passdir):
        assert len(_fixture_files(passdir, "tp_")) >= 3, passdir
        assert len(_fixture_files(passdir, "tn_")) >= 3, passdir

    @pytest.mark.parametrize("passdir,filename",
                             sorted(EXPECTED_RULES),
                             ids=lambda v: v if isinstance(v, str) else None)
    def test_true_positive_fires(self, passdir, filename):
        findings = _run_fixture(passdir, filename)
        rules = {f.rule for f in findings}
        assert EXPECTED_RULES[(passdir, filename)] in rules, (
            f"{passdir}/{filename} produced {rules or 'no findings'}")

    @pytest.mark.parametrize(
        "passdir,filename",
        [(d, f) for d in PASS_DIRS for f in _fixture_files(d, "tn_")])
    def test_true_negative_is_silent(self, passdir, filename):
        findings = _run_fixture(passdir, filename)
        assert findings == [], (
            f"{passdir}/{filename} false-positives:\n"
            + "\n".join(f.format() for f in findings))

    def test_findings_carry_evidence_chains(self):
        findings = _run_fixture("trace_purity", "tp_item_in_jit.py")
        assert findings and findings[0].evidence, \
            "traced finding without its jit-root evidence chain"
        findings = _run_fixture("hotpath", "tp_propagated_callee.py")
        callee = [f for f in findings if "build_record" in f.qualname]
        assert callee and any("called from" in e
                              for e in callee[0].evidence)


# ---------------------------------------------------------------------------
# the repo gate (tier-1: the suite must run clean over the package)
# ---------------------------------------------------------------------------


class TestRepoGate:
    def test_package_is_clean_under_baseline(self):
        unsuppressed, suppressed, _stale = check_clean([PKG])
        assert unsuppressed == [], (
            "unsuppressed swlint findings — fix them or triage into "
            "tools/swlint_baseline.json with a justification:\n"
            + "\n".join(f.format() for f in unsuppressed))
        # the suite is meant to be checking something: the baseline
        # exists and actually suppresses the known worklist
        assert suppressed, "baseline suppressed nothing — wiring broken?"

    def test_every_baseline_entry_is_justified(self):
        baseline = Baseline.load(default_baseline_path())
        assert baseline.entries
        bad = [e for e in baseline.entries
               if not str(e.get("note", "")).strip()
               or str(e["note"]).startswith("TODO")]
        assert not bad, (
            "baseline entries without a justification: "
            + ", ".join(str(e["fp"]) for e in bad))

    def test_traced_set_covers_the_flagship_entrypoints(self):
        """The call graph must actually reach the jit roots the issue
        names — an empty traced set would make TP vacuously clean."""
        project = Project.from_paths([PKG])
        traced = TracePurityPass()._traced_set(project)
        need = ["pipeline.packed.build_packed_chain.chain",
                "pipeline.packed.packed_pipeline_step",
                "pipeline.step.pipeline_step",
                "pipeline.sharded.build_sharded_packed_step.local_step",
                "analytics.windows.aggregate_windows",
                "analytics.query.window_eval",
                # BYO rule-program kernels (rules/compile.py): the
                # structure-keyed group eval + the shared prepare fold
                "rules.compile.rules_group_eval",
                "rules.compile.rules_prepare_batch"]
        for suffix in need:
            assert any(qn.endswith(suffix) for qn in traced), suffix

    def test_hot_path_markers_applied_to_the_per_batch_path(self):
        from sitewhere_tpu.ingest.batcher import Batcher
        from sitewhere_tpu.runtime.dispatcher import PipelineDispatcher
        from sitewhere_tpu.runtime.flightrec import FlightRecorder

        for fn in (PipelineDispatcher._run_ring,
                   PipelineDispatcher._dispatch_plan,
                   PipelineDispatcher._window_step,
                   PipelineDispatcher._egress,
                   PipelineDispatcher._flight_record,
                   FlightRecorder.record,
                   Batcher._emit):
            assert is_hot_path(fn), fn.__qualname__

    def test_hot_path_marker_is_inert(self):
        @hot_path
        def f(x):
            return x + 1

        assert f(1) == 2 and is_hot_path(f)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _cli(*args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO, env=env, **kw)


class TestCli:
    def test_clean_repo_exits_zero(self):
        proc = _cli(os.path.join("sitewhere_tpu"))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 findings" in proc.stdout

    def test_findings_exit_one_and_json_shape(self):
        tp = os.path.join(FIXTURES, "metric_names", "tp_malformed.py")
        proc = _cli(tp, "--no-baseline", "--json",
                    "--passes", "metric-names")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["counts"]["unsuppressed"] == 1
        f = doc["findings"][0]
        for key in ("pass", "rule", "path", "line", "qualname",
                    "message", "fingerprint", "evidence"):
            assert key in f, key
        assert f["rule"] == "MN001"

    def test_update_baseline_roundtrip(self, tmp_path):
        tp = os.path.join(FIXTURES, "hotpath", "tp_list_in_hot.py")
        bl = str(tmp_path / "baseline.json")
        assert _cli(tp, "--baseline", bl, "--passes",
                    "hot-path-alloc").returncode == 1
        up = _cli(tp, "--baseline", bl, "--passes", "hot-path-alloc",
                  "--update-baseline")
        assert up.returncode == 0 and "baseline updated" in up.stdout
        # now suppressed
        proc = _cli(tp, "--baseline", bl, "--passes", "hot-path-alloc")
        assert proc.returncode == 0, proc.stdout
        assert "suppressed by baseline" in proc.stdout

    def test_narrowed_update_preserves_out_of_scope_entries(self, tmp_path):
        """--update-baseline from a run that only covered SOME passes /
        files must not delete entries it never re-checked."""
        hot = os.path.join(FIXTURES, "hotpath", "tp_list_in_hot.py")
        mn = os.path.join(FIXTURES, "metric_names", "tp_malformed.py")
        bl = str(tmp_path / "baseline.json")
        # seed a baseline covering BOTH passes
        assert _cli(hot, mn, "--baseline", bl,
                    "--update-baseline").returncode == 0
        seeded = json.loads(open(bl).read())["entries"]
        assert {e["pass"] for e in seeded} == {"hot-path-alloc",
                                              "metric-names"}
        # narrowed update: one pass, one file
        assert _cli(hot, "--baseline", bl, "--passes", "hot-path-alloc",
                    "--update-baseline").returncode == 0
        after = json.loads(open(bl).read())["entries"]
        assert {e["pass"] for e in after} == {"hot-path-alloc",
                                             "metric-names"}
        # and the full-scope run is still clean under it
        assert _cli(hot, mn, "--baseline", bl).returncode == 0

    def test_update_drops_entries_for_deleted_files(self, tmp_path):
        """A full-scope --update-baseline must prune entries whose file
        no longer exists (stale-forever zombies), while keeping
        entries for existing files merely outside a narrowed path."""
        hot = os.path.join(FIXTURES, "hotpath", "tp_list_in_hot.py")
        bl = str(tmp_path / "baseline.json")
        assert _cli(hot, "--baseline", bl,
                    "--update-baseline").returncode == 0
        doc = json.loads(open(bl).read())
        doc["entries"].append({
            "fp": "feedfacefeedface", "pass": "hot-path-alloc",
            "rule": "HP001", "path": "deleted/gone.py",
            "qualname": "gone.f", "snippet": "", "note": "zombie"})
        open(bl, "w").write(json.dumps(doc))
        assert _cli(hot, "--baseline", bl,
                    "--update-baseline").returncode == 0
        after = json.loads(open(bl).read())["entries"]
        assert all(e["path"] != "deleted/gone.py" for e in after), after

    def test_no_baseline_update_refused(self):
        proc = _cli("sitewhere_tpu", "--no-baseline", "--update-baseline")
        assert proc.returncode == 2
        assert "refusing" in proc.stderr

    def test_marker_import_does_not_load_the_suite(self):
        """Production modules import only the inert marker; the AST
        passes must stay unloaded (analysis/__init__ is lazy)."""
        code = ("import sys; import sitewhere_tpu.analysis.markers; "
                "bad = [m for m in sys.modules if "
                "m.startswith('sitewhere_tpu.analysis.') and "
                "not m.endswith('.markers')]; "
                "assert not bad, bad")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=120,
                              cwd=REPO,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stderr

    def test_unknown_pass_and_missing_path(self):
        assert _cli("sitewhere_tpu", "--passes", "nope").returncode == 2
        assert _cli("definitely/missing.py").returncode == 2

    def test_list_passes(self):
        proc = _cli("--list-passes")
        assert proc.returncode == 0
        for pass_id in ("trace-purity", "lock-discipline", "donation",
                        "hot-path-alloc", "metric-names"):
            assert pass_id in proc.stdout


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


class TestBaseline:
    def _finding(self, line, snippet="x = donated.sum()"):
        return Finding(pass_id="donation", rule="DN001", path="mod.py",
                       line=line, qualname="mod.f", message="m",
                       snippet=snippet)

    def test_fingerprint_survives_line_shifts(self):
        a, b = self._finding(10), self._finding(99)
        assert a.fingerprint == b.fingerprint

    def test_fingerprint_tracks_the_expression(self):
        a = self._finding(10, "x = donated.sum()")
        b = self._finding(10, "y = donated.mean()")
        assert a.fingerprint != b.fingerprint

    def test_apply_splits_and_reports_stale(self, tmp_path):
        f1, f2 = self._finding(1), self._finding(2, "other = donated[0]")
        bl = Baseline.from_findings([f1], note="known worklist entry")
        bl.entries.append({"fp": "deadbeefdeadbeef", "pass": "donation",
                           "rule": "DN001", "path": "gone.py",
                           "qualname": "gone.f", "snippet": "",
                           "note": "obsolete"})
        unsup, sup, stale = bl.apply([f1, f2])
        assert [f.fingerprint for f in sup] == [f1.fingerprint]
        assert [f.fingerprint for f in unsup] == [f2.fingerprint]
        assert len(stale) == 1 and stale[0]["fp"] == "deadbeefdeadbeef"
        path = str(tmp_path / "b.json")
        bl.save(path)
        assert Baseline.load(path).fingerprints == bl.fingerprints

    def test_update_preserves_existing_notes(self):
        f1 = self._finding(1)
        old = Baseline.from_findings([f1], note="hand-written reason")
        new = Baseline.from_findings([f1, self._finding(2, "z = donated")],
                                     old=old)
        notes = {e["fp"]: e["note"] for e in new.entries}
        assert notes[f1.fingerprint] == "hand-written reason"
        assert any(n.startswith("TODO") for n in notes.values())


# ---------------------------------------------------------------------------
# the shared metric-name contract (folded dynamic lint)
# ---------------------------------------------------------------------------


class TestLintNamesHelper:
    def test_clean_names(self):
        assert lint_names(["pipeline.steps", "ingest.batch_wait_s",
                           "device.occupancy.rows_admitted",
                           "device.stage_ms.full",
                           "slo.burn_rate.p99_ms.fast",
                           "flightrec.records",
                           "pipeline.bytes_copied.h2d",
                           "native.build_fallbacks"]) == []

    def test_violations(self):
        bad = lint_names(["Bad Name", "flightrec.snapshot",
                          "pipeline.bytes_copied.total",
                          "device.thermals.max_c"])
        assert len(bad) == 4
        assert any("convention" in m for m in bad)
        assert any("closed" in m and "flightrec" in m for m in bad)
        assert any("no declared family" in m for m in bad)


# ---------------------------------------------------------------------------
# regressions for the two findings the suite surfaced and fixed
# ---------------------------------------------------------------------------


class TestFixedFindings:
    def test_state_manager_queries_never_hold_lock_through_d2h(self):
        """Fix 1 (swlint LK004): missing/seen_since/summary snapshot the
        epoch under the lease lock and transfer OUTSIDE it.  Lint-level
        regression: the lock pass over state/manager.py must not flag
        the query methods; behavioral: results stay correct."""
        findings = LockDisciplinePass().run(Project.from_paths(
            [os.path.join(PKG, "state")], root=REPO))
        flagged = {f.qualname.rsplit(".", 1)[-1]
                   for f in findings if f.rule == "LK004"}
        assert not flagged & {"missing_device_ids", "seen_since",
                              "summary"}, findings

        from sitewhere_tpu.ids import IdentityMap
        from sitewhere_tpu.state.manager import DeviceStateManager

        mgr = DeviceStateManager(capacity=8, identity=IdentityMap(8))
        state = mgr.current
        state = state.replace(
            last_event_type=state.last_event_type.at[2].set(0),
            last_event_ts_s=state.last_event_ts_s.at[2].set(1000),
            presence_missing=state.presence_missing.at[5].set(True))
        mgr.commit(state)
        assert mgr.missing_device_ids() == [5]
        assert mgr.seen_since(500) == [2]
        assert mgr.summary() == {"devices_with_state": 1,
                                 "devices_missing": 1}

    def test_batcher_emit_defers_device_transfers(self):
        """Fix 2 (swlint LK004): the unpacked ``_emit`` no longer builds
        the device EventBatch under the intake lock — plans carry numpy
        ``host_cols`` and materialize lazily, bit-identically."""
        findings = LockDisciplinePass().run(Project.from_paths(
            [os.path.join(PKG, "ingest")], root=REPO))
        emit_h2d = [f for f in findings if f.rule == "LK004"
                    and f.qualname.endswith("._emit")]
        assert not emit_h2d, emit_h2d

        from sitewhere_tpu.ingest.batcher import Batcher

        b = Batcher(width=4, n_shards=1, registry_capacity=16,
                    resolve_device=int, resolve_mtype=lambda s: 0,
                    resolve_alert=lambda s: 0)
        plans = b.add_arrays(device_id=np.arange(4, dtype=np.int32),
                             value=np.full(4, 2.5, np.float32))
        assert len(plans) == 1
        plan = plans[0]
        # emission did NO device work: the EventBatch is unmaterialized
        assert plan._batch is None and plan.host_cols
        batch = plan.batch          # first access materializes + caches
        assert batch is plan.batch
        assert np.array_equal(np.asarray(batch.device_id),
                              np.arange(4, dtype=np.int32))
        assert np.allclose(np.asarray(batch.value), 2.5)
        assert np.asarray(batch.valid).all()

    def test_packed_plans_do_not_materialize_an_eventbatch(self):
        from sitewhere_tpu.ingest.batcher import Batcher

        b = Batcher(width=4, n_shards=1, registry_capacity=16,
                    resolve_device=int, resolve_mtype=lambda s: 0,
                    resolve_alert=lambda s: 0)
        (plan,) = b.add_arrays(device_id=np.arange(4, dtype=np.int32))
        # emission (under the intake lock) builds no device EventBatch;
        # ``plan.batch`` is a reader's view, built on first access
        assert plan.packed_i is not None and plan._batch is None

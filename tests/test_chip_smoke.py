"""chip_smoke.py's body at toy size on CPU, and its refusal to run there.

The script itself only ever runs on the chip (``python chip_smoke.py``
through the chip tool); these tests keep its workload, its numpy
reference and its checks from rotting between chip runs.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

TOY = dict(capacity=4096, width=256, n_devices=512, ring_depth=2,
           wire_windows=2, wire_payloads=4, wire_lines=64,
           column_batches=12, sample=32)


@pytest.fixture(scope="module")
def toy_run():
    checks, meter = chip_smoke.Checks(), chip_smoke.CompileMeter()
    report = chip_smoke.run_leg(checks, meter, seed=3, **TOY)
    return checks, report


def test_both_legs_match_numpy_with_the_ring_forced(toy_run):
    checks, report = toy_run
    assert checks.failed == []
    wire, column = report["wire"], report["column"]
    assert wire["events"] == 2 * 4 * 64
    # wire traffic never fills a plan: single-step path only
    assert wire["ring_chains"] == 0 and wire["steps"] >= 2
    # 12 full-width batches through a forced K=2 ring
    assert column["ring_chains"] >= 2
    assert column["host_syncs"] < column["steps"]
    assert column["threshold_alerts"] > 0 and column["zone_alerts"] > 0
    assert report["registered"] == 512
    assert report["switches"]["ring_depth"] == 2
    # the boot warm-up compiled every program live traffic dispatched
    assert wire["programs"] == 0 and column["programs"] == 0


def test_chip_side_expectations_reject_the_cpu_sides(toy_run):
    """The CPU toy run passes its comparisons but must NOT pass for a
    chip run: the backend switches sat on their CPU sides."""
    _, report = toy_run
    checks = chip_smoke.Checks()
    chip_smoke.check_chip_side(checks, report)
    failed = " ".join(checks.failed)
    for name in ("ring_depth", "ring_donate", "egress_offload",
                 "cost_analysis", "batch_staging", "staged ahead"):
        assert name in failed, (name, checks.failed)


def test_mesh_leg_places_the_fleet_on_every_shard(devices):
    checks, meter = chip_smoke.Checks(), chip_smoke.CompileMeter()
    report = chip_smoke.run_leg(checks, meter, n_shards=4,
                                **dict(TOY, sample=8))
    assert checks.failed == []
    assert report["column"]["ring_chains"] >= 2
    # warm-up slots are placed like live ones: a mesh chain that arrives
    # with another sharding would be another program, compiled live
    assert report["wire"]["programs"] == 0
    assert report["column"]["programs"] == 0


def test_pallas_check_compares_with_the_dense_path():
    checks = chip_smoke.Checks()
    doc = chip_smoke.pallas_check(checks, 300, 130, 16, seed=1,
                                  interpret=True)
    assert checks.failed == [] and doc["mismatches"] == 0


def test_entry_refuses_a_platform_that_is_not_tpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    monkeypatch.setattr(
        chip_smoke, "run_leg",
        lambda *a, **k: pytest.fail("workload ran without a TPU"))
    assert chip_smoke.main() == 1
    out = capsys.readouterr().out
    assert out.splitlines()[0].startswith("platform: cpu")
    assert '"ok"' not in out


def test_a_failed_check_is_kept_and_printed(capsys):
    checks = chip_smoke.Checks()
    assert checks.equal("stored", 3, 3)
    assert not checks.equal("zone alerts", 2, 3)
    assert checks.failed == ["zone alerts"]
    assert "[FAIL] zone alerts: got 2, want 3" in capsys.readouterr().out

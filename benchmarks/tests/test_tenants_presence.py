"""``tenants-8-presence`` and its cell ``tenants-8-presence.wire-tenants``:
the files and entries resolve, the cell's body runs at toy size on the
CPU and reads ``correct: true`` against the kind's plain reference, each
of the kind's controls reads ``correct: false`` at the comparison it
names, and the new readers give nothing where their timer or counter is
absent (the four older cells, a parent program)."""

import time

import numpy as np
import pytest

from benchmarks import cells, control, harness
from benchmarks.harness import Run
from benchmarks.tests.toy import toy_cell

CONFIG = "tenants-8-presence"
CELL = "tenants-8-presence.wire-tenants"
NEW = {"presence_sweep_ms": "presence.sweep_s",
       "presence_sweeps_in_window": "presence.sweeps",
       "presence_reported_in_window": "presence.reported",
       "tenant_meter_ms_per_plan": "pipeline.stage_meter_s",
       "wire_tenant_rows_share": "ingest.wire_rows_tenant"}
# span metrics whose list of cells ``test_span_metrics.py`` holds to the two
# wire-steady cells: not this PR's to widen, so the new cell goes without
HELD_TO_WIRE_STEADY = {"wire_device_wait_ms_per_plan",
                       "wire_egress_host_ms_per_plan",
                       "wire_inflight_wait_ms_per_plan",
                       "journal_ms_per_payload"}
FAULTS = {"one-tenants-rows-as-the-others": "unregistered",
          "another-missing-after": "stored STATE_CHANGE events",
          "replay-forgets-the-tenant": "replayed rows refused"}


def toy() -> dict:
    """The cell cut as every toy is, sweeping twice a second: the
    cohorts then cross a second apart and the run ends in ten."""
    cell = toy_cell(CELL)
    cell["config"]["config"]["presence"]["scan_interval_s"] = 0.5
    cell["traffic"]["flag_wait_s"] = 5.0
    return cell


def test_the_configuration_is_fleet_1m_under_eight_tenants():
    doc = cells.resolve_cell(CELL)["config"]
    sibling = cells.resolve_cell("fleet-1m.wire-steady")["config"]
    for key in ("measurement", "calibration", "sample_devices", "programs"):
        assert doc[key] == sibling[key], key
    for group in ("pipeline", "journal", "overload", "checkpoint"):
        assert doc["config"][group] == sibling["config"][group], group
    assert doc["config"]["presence"] == {"missing_after_s": 28800,
                                         "scan_interval_s": 5.0}
    sizes = [t["devices"] for t in doc["tenants"]]
    assert sizes == [65536, 32768, 16384, 8192, 4096, 2048, 1024, 1024]
    assert doc["fleet"]["devices"] == sum(sizes) == 131072
    rules = doc["rules"]
    assert [r["tenant"] for r in rules["thresholds"]] \
        == [t["token"] for t in doc["tenants"]]
    assert [r["threshold"] for r in rules["thresholds"]] \
        == [98.6, 98.7, 98.8, 98.9, 99.0, 99.1, 99.2, 99.3]
    assert rules["zones"] == []
    assert doc["presence"] == {"silent_cohorts": 8,
                               "silent_cohort_devices": 256}
    assert set(sibling["guarantees"]) | {"tenants", "presence"} \
        == set(doc["guarantees"])
    entry = {c["name"]: c for c in cells.load_benchmark()["configs"]}[CONFIG]
    assert entry["reduced"] == doc["reduced"] \
        == ["tenants.devices", "presence.scan_interval_s"]
    assert entry["source"] == doc["source"] and len(entry["source"]) <= 200


def test_the_cell_resolves_with_its_kind_its_mix_and_its_metrics():
    cell = cells.resolve_cell(CELL)
    assert cell["chips"] == 1
    kind = cells.load_kind(cell["config"])
    assert all(hasattr(kind, name) for name in cells.KIND_SUPPLIES)
    assert sorted(kind.FAULTS) == sorted(FAULTS)
    traffic = cell["traffic"]
    assert traffic["kind"] == "wire-tenants-open-loop"
    assert (traffic["lines_per_payload"], traffic["pool_payloads"],
            traffic["senders"], traffic["prime_sends"],
            traffic["probe_every"], traffic["trace_seconds"]) \
        == (1024, 64, 4, 4, 64, 6.0)
    assert traffic["rate_events_per_s"] in (640.0, 1280.0, 2560.0, 5120.0,
                                            10240.0, 20480.0) \
        or traffic["rate_events_per_s"] * 1.25 in (
            640.0, 1280.0, 2560.0, 5120.0, 10240.0, 20480.0)
    assert {e["name"] for e, _ in cell["end_to_end"]} \
        == {"events_per_s", "latency_p50_ms", "setup_s"}
    sibling = {e["name"] for e, _ in
               cells.resolve_cell("fleet-1m.wire-steady")["per_layer"]}
    assert {e["name"] for e, _ in cell["per_layer"]} \
        == (sibling - HELD_TO_WIRE_STEADY) | set(NEW)
    for entry, _ in cell["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "latency_p50_ms"


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_new_reader_gives_nothing_where_its_source_is_absent(metric):
    read = cells.reader("layer_metrics", metric)
    assert read(Run(marks0={}, marks1={})) is None
    # registered, never observed in the window: nothing to divide by
    idle = {NEW[metric]: (0.0, 0) if NEW[metric].endswith("_s") else 0,
            "ingest.wire_rows": 0}
    assert read(Run(marks0=dict(idle), marks1=dict(idle))) in (None, 0)


def test_reader_arithmetic():
    marks0 = {"presence.sweep_s": (0.010, 2),
              "presence.sweep_device_s": (0.004, 2),
              "presence.sweeps": 2, "presence.reported": 256,
              "pipeline.stage_meter_s": (0.001, 10),
              "ingest.wire_rows": 4096, "ingest.wire_rows_tenant": 4096}
    marks1 = {"presence.sweep_s": (0.050, 10),
              "presence.sweep_device_s": (0.020, 10),
              "presence.sweeps": 10, "presence.reported": 2048,
              "pipeline.stage_meter_s": (0.011, 210),
              "ingest.wire_rows": 208896, "ingest.wire_rows_tenant": 208896}
    run = Run(marks0=marks0, marks1=marks1)

    def read(metric):
        return cells.reader("layer_metrics", metric)(run)

    assert read("presence_sweep_ms") == pytest.approx(5.0)
    assert read("presence_sweeps_in_window") == 8
    assert read("presence_reported_in_window") == 1792
    assert read("tenant_meter_ms_per_plan") == pytest.approx(0.05)
    assert read("wire_tenant_rows_share") == 100.0


class _Dep:
    """What the traffic kind reads of a populated deployment."""

    def __init__(self, cell):
        self.config = cell["config"]
        sizes = [64, 32, 16]
        self.tenant_ids = {f"t{i}": i + 1 for i in range(3)}
        owner, cohort = [], []
        for t, n in enumerate(sizes):
            owner += [t + 1] * (n + 4)
            cohort += [-1] * n + [0, 0, 1, 1]
        self.owner = np.asarray(owner, np.int32)
        self.cohort = np.asarray(cohort, np.int32)
        self.tokens = [f"d{i}" for i in range(len(owner))]
        self.handles = np.arange(len(owner), dtype=np.int32) + 7


def test_the_seed_makes_the_traffic_and_a_payload_is_one_tenants():
    cell = toy()
    kind = cells.load_module(cell["traffic"]["kind_file"])
    params = dict(cell["traffic"], lines_per_payload=16, pool_payloads=200)
    dep = _Dep(cell)
    a, b, a2 = (kind.build(params, dep, np.random.default_rng(s))
                for s in (1, 2, 1))
    assert a.payloads == a2.payloads and a.named == a2.named
    assert a.payloads[0] != b.payloads[0]
    assert not np.array_equal(a.order, b.order)
    owner_of = np.full(256, -1, np.int32)
    owner_of[dep.handles] = dep.owner
    pool = a.bodies[:200]
    for body in pool:                  # one tenant's, no device twice
        assert (owner_of[body["dev"]] == body["tenant"]).all()
        assert len(set(body["dev"].tolist())) == len(body["dev"]) == 16
    drawn = np.bincount([int(body["tenant"][0]) for body in pool],
                        minlength=4)[1:] / 200.0
    assert np.allclose(drawn, [64 / 112, 32 / 112, 16 / 112], atol=0.12)
    for i in a.wrong:                  # a probe names another tenant
        body = a.bodies[i]
        assert (owner_of[body["dev"]] != body["tenant"]).all()
    # the cohorts: a payload a tenant, never a live device
    assert [len(c) for c in a.cohorts] == [3, 3]
    named = {int(h) for body in pool for h in body["dev"]}
    silent = set(dep.handles[dep.cohort >= 0].tolist())
    assert not named & silent
    assert a.first == 6 + params["prime_sends"] + 1


@pytest.fixture(scope="module")
def sound():
    seen = {}
    result = harness.run_cell(
        toy(), 3, 2.0, True, time.perf_counter(), require_tpu=False,
        on_run=lambda run: seen.update(run=run))
    return result, seen["run"]


def test_the_cell_at_toy_size_reads_correct(sound):
    result, run = sound
    assert result["correct"] is True, control.failed_comparisons(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert compared["programs compiled inside the window"] == [0, 0]
    assert compared["stored STATE_CHANGE events = devices gone silent"] \
        == [2048, 2048]
    refused = compared["rows dead-lettered as unregistered"][1]
    assert refused > 0 and refused % 64 == 0
    assert compared["pipeline.bytes_copied.decode (native fill-direct "
                    "decode)"] == [0, 0]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["wire_tenant_rows_share"] == 100.0
    assert metrics["presence_sweeps_in_window"] >= 3
    assert metrics["presence_reported_in_window"] % 256 == 0
    assert metrics["presence_sweep_ms"] > 0
    assert metrics["tenant_meter_ms_per_plan"] > 0


def test_the_probes_are_sent_and_never_counted_as_load(sound):
    sends = sound[1].sends
    made = sends.n > 0
    probes = made & ~sends.measured & (sends.sent >= sound[1].t_begin)
    assert probes.sum() >= 1
    assert (sound[1].delivery.delivered[np.nonzero(probes)[0]] == 0).all()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_control_reads_not_correct(fault):
    doc = control.run_control(toy(), fault, 3, 2.0, time.perf_counter(),
                              require_tpu=False)
    assert doc["correct"] is False
    assert doc["must_fail"] == FAULTS[fault]
    assert doc["seen"], doc["failed_comparisons"]

"""BENCHMARK.json against the letter of the contract, and every file a
cell or a metric names."""

import ast
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.REPO, "BENCHMARK.json")) \
        < 64 << 10
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_names_units_and_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmarks/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for group in (metrics, BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    assert {c["name"] for c in BENCH["configs"]} \
        == {w["config"] for w in BENCH["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", CELLS)
def test_every_file_of_a_cell_resolves_by_name(name):
    cell = cells.resolve_cell(name)
    assert os.path.exists(cell["traffic"]["kind_file"])
    assert cell["config"]["config"]["pipeline"]["n_shards"] == cell["chips"]
    e2e = [entry["name"] for entry, _ in cell["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for entry, path in cell["end_to_end"] + cell["per_layer"]:
        assert callable(cells.load_module(path).read), path
    # a per-layer metric is reported only where the metric it moves is
    assert {entry["moves"] for entry, _ in cell["per_layer"]} <= set(e2e)
    if cell["traffic"]["kind"] == "wire-open-loop":
        assert cell["traffic"]["rate_events_per_s"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_a_cells_own_file_carries_only_what_its_mix_takes(name):
    """``cells/<cell>.json`` lays a rate over an open-loop mix or a depth
    over a closed-loop one, never the other's, and says where the number
    comes from; a depth in the mix itself says so too."""
    cell = cells.resolve_cell(name)
    traffic = cell["traffic"]
    own_path = os.path.join(cells.HERE, "cells", name + ".json")
    own = cells.load_json(own_path) if os.path.exists(own_path) else {}
    for number, origin in (("clients", "clients_from"),
                           ("rate_events_per_s", "rate_from")):
        assert (number in own) <= (origin in own), (name, origin)
        if number in traffic:
            assert isinstance(traffic.get(origin), str) and traffic[origin]
    # the cell's file is laid over the mix, so this holds both to it
    if traffic["kind"].endswith("closed-loop"):
        assert int(traffic["clients"]) >= 1
        assert "rate_events_per_s" not in traffic
    else:
        assert "clients" not in traffic


CONFIG_FILES = {c["name"]: cells.load_json(os.path.join(cells.REPO, c["file"]))
                for c in BENCH["configs"]}


def _kinds_in(folder: str) -> set:
    return {f[:-3] for f in os.listdir(os.path.join(cells.HERE, folder))
            if f.endswith(".py")}


@pytest.mark.parametrize("config", sorted(CONFIG_FILES))
def test_every_configuration_names_a_kind_that_supplies_the_four_things(
        config):
    """The fleet and its rules, the plain reference, a send's own rows
    with the comparisons, the controls: ``cells.KIND_SUPPLIES``."""
    doc = CONFIG_FILES[config]
    assert NAME.match(doc["kind"])
    assert os.path.exists(cells.kind_file(doc["kind"]))
    kind = cells.load_kind(doc)            # raises on a missing one
    for name in ("populate", "own_rows", "compare", "compare_intake"):
        assert callable(getattr(kind, name)), name
    assert kind.reference.__file__ == cells.reference_file(doc["kind"])
    assert kind.FAULTS
    for fault, (when, plant, must_fail) in kind.FAULTS.items():
        assert NAME.match(fault)
        assert when in ("before", "after") and callable(plant)
        assert isinstance(must_fail, str) and must_fail


def test_no_kind_is_without_a_configuration_and_no_reference_without_a_kind():
    named = {doc["kind"] for doc in CONFIG_FILES.values()}
    assert _kinds_in(os.path.join("configs", "kinds")) == named
    assert _kinds_in(os.path.join("configs", "references")) == named


@pytest.mark.parametrize("kind", sorted(
    _kinds_in(os.path.join("configs", "references"))))
def test_a_plain_reference_imports_nothing_of_the_program(kind):
    with open(cells.reference_file(kind)) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "numpy"}, imported


def test_the_harness_names_nothing_that_is_a_kinds():
    """No rule family, event kind or fault, and no import of a
    reference, in the files that serve every deployment."""
    kinds_own = ["EventType", "ALERT", "STATE_CHANGE", "thresholds", "zones",
                 "create_rule", "create_zone", "import reference",
                 "cells, reference"]
    for doc in CONFIG_FILES.values():
        kinds_own += list(cells.load_kind(doc).FAULTS)
    for name in ("harness.py", "deployment.py", "control.py", "run.py"):
        with open(os.path.join(cells.HERE, name)) as f:
            text = f.read()
        assert [w for w in kinds_own if w in text] == [], name


def test_metric_and_kind_files_all_belong_to_an_entry():
    here = os.path.join(cells.REPO, "benchmarks")
    for folder, section in (("end_to_end", "end_to_end"),
                            ("layer_metrics", "per_layer")):
        files = {f[:-3] for f in os.listdir(os.path.join(here, folder))
                 if f.endswith(".py")}
        assert files == {m["name"] for m in BENCH[section]}


def test_run_names_no_cell_configuration_mix_or_metric():
    with open(os.path.join(cells.REPO, "benchmarks", "run.py")) as f:
        text = f.read()
    words = ([w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert [w for w in words if w in text] == []


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result():
    done = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=cells.REPO, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    last = done.stdout.strip().splitlines()[-1]
    with pytest.raises(ValueError):
        json.loads(last)

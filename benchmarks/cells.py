"""Everything a cell is made of, found by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; a metric names its
reader.  Each lives in a file of its own so that a later PR adds a
cell, a mix or a metric by adding files and entries, never by editing
one that is there:

- ``configs/<config>.json`` (the path is the entry's ``file``), its
  ``kind`` → ``configs/kinds/<kind>.py``: what differs from deployment
  to deployment and the harness asks a kind for (``KIND_SUPPLIES``),
  with the kind's plain reference in ``configs/references/<kind>.py``
- ``traffic/<traffic>.json``, its ``kind`` → ``traffic/kinds/<kind>.py``
- ``cells/<cell>.json``, optional: the cell's own parameters (the rate
  of an open loop), laid over the mix
- ``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py``:
  ``read(run)`` → a number, or None where there is nothing to read
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# What a configuration's kind supplies, and all the harness asks of it:
# - ``populate(dep)``: the fleet and its rules, between the instance's
#   start and the calibration; leaves ``dep.tokens`` and ``dep.handles``
#   and what else its traffic kinds read;
# - ``reference``: its plain reference (``reference_of``), a module that
#   imports nothing of the program;
# - ``own_rows(cols)``: which delivered rows are a send's own, and
#   ``compare(checks, dep, traffic, run)``: the comparisons with the
#   reference that decide ``correct``.  It returns how many dead letters
#   its reference accounts for (rows refused by design), or None.
#   ``compare_intake(...)`` is the one on the path the sends took in,
#   made between the harness's own so that ``compared`` keeps its order;
# - ``FAULTS``: its controls, fault → (planted "before" or "after"
#   populate, how, the start of the name of a comparison that then has
#   to fail).
KIND_SUPPLIES = ("populate", "reference", "own_rows", "compare",
                 "compare_intake", "FAULTS")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE)[:-3].replace(
        os.sep, "_").replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(folder: str, name: str):
    """``read`` of the metric file ``<folder>/<name>.py`` beside this
    one: for a metric that is another's reading under a name of its own
    (one entry of BENCHMARK.json has one ``moves``)."""
    return load_module(os.path.join(HERE, folder, name + ".py")).read


def kind_file(kind: str, here: str = HERE) -> str:
    return os.path.join(here, "configs", "kinds", kind + ".py")


def reference_file(kind: str, here: str = HERE) -> str:
    return os.path.join(here, "configs", "references", kind + ".py")


def reference_of(kind_path: str):
    """The plain reference of the kind whose file is ``kind_path``:
    ``../references/<the same name>``, also in a copy of the
    repository."""
    return load_module(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(kind_path))),
        "references", os.path.basename(kind_path)))


def load_kind(config: dict):
    """The kind of a configuration's file: from ``kind_file`` where
    ``resolve_cell`` set it (a copy of the repository), else beside
    this module."""
    kind = load_module(config.get("kind_file") or kind_file(config["kind"]))
    missing = [name for name in KIND_SUPPLIES if not hasattr(kind, name)]
    if missing:
        raise AttributeError(f"kind {config['kind']!r} supplies no "
                             f"{', '.join(missing)}")
    return kind


def load_benchmark(repo: str = REPO) -> dict:
    return load_json(os.path.join(repo, "BENCHMARK.json"))


def resolve_cell(name: str, repo: str = REPO) -> dict:
    """The cell ``name`` of ``<repo>/BENCHMARK.json`` with its files
    read: ``{"name", "chips", "config", "traffic", "end_to_end",
    "per_layer"}`` — the two metric lists as (entry, reader path)."""
    bench = load_benchmark(repo)
    here = os.path.join(repo, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(cells))})")
    cell = cells[name]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(repo, config_entry["file"]))
    config["kind_file"] = kind_file(config["kind"], here)
    traffic = load_json(os.path.join(here, "traffic",
                                     cell["traffic"] + ".json"))
    own = os.path.join(here, "cells", name + ".json")
    if os.path.exists(own):
        traffic.update(load_json(own))
    traffic["kind_file"] = os.path.join(here, "traffic", "kinds",
                                        traffic["kind"] + ".py")

    def readers(section: str, folder: str) -> list:
        out = []
        for m in bench[section]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            out.append((m, os.path.join(here, folder, m["name"] + ".py")))
        return out

    return {"name": name, "chips": int(cell["chips"]), "config": config,
            "traffic": traffic,
            "end_to_end": readers("end_to_end", "end_to_end"),
            "per_layer": readers("per_layer", "layer_metrics")}

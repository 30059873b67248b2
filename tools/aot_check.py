"""AOT-compile the chip side of the device programs with no chip present.

The installed libtpu can describe a v5e topology without hardware
(``jax.experimental.topologies.get_topology_desc``), and XLA compiles
against it ahead of time.  With ``jax.default_backend`` patched to
return ``"tpu"`` while tracing, every backend switch in the tree takes
its chip side, so a change that breaks the TPU lowering of

- the packed single step (donated, as served on the chip),
- the K-step donated chain (``build_packed_chain``),
- the mesh-fused chain on the 2x2 mesh (``build_sharded_packed_chain``),
- the Pallas geofence kernel with ``interpret=False``

shows up here, on a CPU-only machine, before any chip time is spent.
For each program it prints ``memory_analysis()`` (argument / output /
temp bytes per chip) and the ``cost_analysis()`` totals.  These are
compile-time ESTIMATES, not measurements: time, utilization and peak
memory come from ``chip_smoke.py`` on the chip.

    python tools/aot_check.py                       # toy size, seconds
    python tools/aot_check.py --capacity 1048576 --width 65536   # shipped

tests/test_aot_check.py runs the toy size in tier-1.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY = "v5e:2x2"
# published table shapes with <= 8 rules / zones (schema.pow2_at_least
# floors the trimmed tables at 8; RegistryMirror pads rings to 32 verts)
RULES, ZONES, VERTS = 8, 8, 32

_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
                "collective-permute", "reduce-scatter")


@contextlib.contextmanager
def chip_side_tracing():
    """Make ``jax.default_backend()`` answer ``"tpu"`` while tracing, so
    the ``default_backend()`` switches (the Pallas geofence threshold)
    lower the side the chip runs."""
    import jax

    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = real


def _abstract(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _packed_avals(capacity: int, width: int, mtype_slots: int):
    """Shapes of ``(tables, state, bi, bf)`` with nothing allocated."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.pipeline.packed import (
        BATCH_F,
        BATCH_I,
        pack_state,
        pack_tables,
    )
    from sitewhere_tpu.schema import (
        DeviceState,
        Registry,
        RuleTable,
        ZoneTable,
    )

    tables = jax.eval_shape(lambda: pack_tables(
        Registry.empty(capacity), RuleTable.empty(RULES),
        ZoneTable.empty(ZONES, max_verts=VERTS)))
    state = jax.eval_shape(
        lambda: pack_state(DeviceState.empty(capacity, mtype_slots)))
    bi = jax.ShapeDtypeStruct((len(BATCH_I), width), jnp.int32)
    bf = jax.ShapeDtypeStruct((len(BATCH_F), width), jnp.float32)
    return tables, state, bi, bf


_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")
# how a registry-sized value may come to be: handed on, or updated in place
_HANDED_ON = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
              "call", "conditional", "optimization-barrier"}
_CARRY_OPS = _HANDED_ON | {"fusion", "scatter", "dynamic-update-slice"}
_COPY_OPS = {"copy", "copy-start"}
_TABLE_OPS = _HANDED_ON | {"copy-start", "copy-done"}


def assert_step_costs_the_batch(hlo: str, capacity: int, donated: bool,
                                mtype_slots: int = 8) -> None:
    """Hold a compiled step program (its HLO text) to the batch: every
    value with at least ``capacity`` elements must be

    - the packed carry ``[capacity, W]`` (or its flat bitcast), handed
      on or updated in place (``scatter`` / ``dynamic-update-slice``),
      copied at most once where the carry is not ``donated`` and never
      where it is (a small carry the chip's compiler stages through
      fast memory, ``S(1)``, may move in and out of it once besides);
    - the packed registry table (8 x capacity elements), only read;
    - a vector of ``capacity`` elements (``present_now``: its zero fill,
      its one scatter, the chain's OR and the telemetry count).

    Anything else — a ``[capacity x M, k]`` pack, a rewritten state
    column, a ``[capacity]`` map per family — raises ``AssertionError``.
    Batch-sized values must stay under ``capacity`` elements for this to
    tell them apart: lower at a width of 64 or so.
    """
    from sitewhere_tpu.pipeline.packed import packed_row_width

    carry = capacity * packed_row_width(mtype_slots, 3)
    table = capacity * 8
    copies, staged, offenders = 0, 0, []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        shapes, op = m.groups()
        for _, dims in _SHAPE.findall(shapes):
            sizes = [int(d) for d in dims.split(",") if d]
            n = 1
            for d in sizes:
                n *= d
            if n < capacity:
                continue
            if n == capacity and max(sizes) == capacity:
                continue                      # a [capacity] vector
            if n == carry and op in _CARRY_OPS:
                continue
            if n == carry and op in _COPY_OPS:
                if "S(1)" in line:
                    staged += 1
                else:
                    copies += 1
                break                  # one instruction, one copy
            if n == carry and op == "copy-done":
                continue
            if n == table and op in _TABLE_OPS:
                continue
            offenders.append(f"{op} {shapes.strip()[:80]}")
    if offenders:
        raise AssertionError(
            f"values sized by the registry (capacity {capacity}): "
            + "; ".join(sorted(set(offenders))[:8]))
    allowed = 0 if donated else 1
    if staged > 2 or copies > allowed:
        raise AssertionError(
            f"{copies} copies of the carry (and {staged} through fast "
            f"memory), sized by the registry (capacity {capacity}); at most "
            f"{allowed} {'with' if donated else 'without'} donation")


def compiled_hlo(program: str, capacity: int, width: int,
                 mtype_slots: int = 8, ring_depth: int = 8) -> str:
    """Optimized HLO of ``packed_step`` or ``packed_chain_k<K>_donated``
    compiled for the DEFAULT backend (the CPU in tier-1) from shapes."""
    import jax

    from sitewhere_tpu.pipeline.packed import (
        build_packed_chain,
        build_packed_step,
    )

    tables, state, bi, bf = _packed_avals(capacity, width, mtype_slots)
    if program == "packed_step":
        lowered = build_packed_step(donate=True).lower(tables, state, bi, bf)
    else:
        k = ring_depth
        lowered = build_packed_chain(k, donate=True).lower(
            tables, state, *([bi] * k), *([bf] * k))
    return lowered.compile().as_text()


def _report(name: str, lowered, t0: float) -> Dict[str, object]:
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    row = {
        "program": name,
        "compile_s": round(time.perf_counter() - t0, 2),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collectives": {
            op: n for op in _COLLECTIVES
            if (n := len(re.findall(rf"= \S+ {op}(?:-start)?\(", hlo)))},
    }
    print(json.dumps(row), flush=True)
    row["hlo"] = hlo
    return row


def aot_check(capacity: int = 4096, width: int = 1024, ring_depth: int = 8,
              mtype_slots: int = 8,
              pallas_shape: Tuple[int, int, int] = (4096, 100, 8),
              programs: Tuple[str, ...] = (
                  "packed_step", "packed_chain", "sharded_chain",
                  "geo_pallas"),
              ) -> List[Dict[str, object]]:
    """Compile the chip-side ``programs`` (all four by default) for the
    v5e topology; returns one row per program, its optimized HLO under
    ``"hlo"`` (raises if any fails to lower or compile)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    from sitewhere_tpu.ops.geo_pallas import points_in_polygons_pallas
    from sitewhere_tpu.parallel.mesh import MODEL_AXIS, SHARD_AXIS
    from sitewhere_tpu.pipeline.packed import (
        build_packed_chain,
        build_packed_step,
    )
    from sitewhere_tpu.pipeline.sharded import (
        _PACKED_BATCH_SPEC,
        _PACKED_STATE_SPEC,
        _packed_tables_specs,
        build_sharded_packed_chain,
    )
    from sitewhere_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = topologies.get_topology_desc(TOPOLOGY, "tpu").devices
    one = SingleDeviceSharding(devices[0])
    mesh = Mesh(np.asarray(devices).reshape(len(devices), 1),
                (SHARD_AXIS, MODEL_AXIS))
    tables, state, bi, bf = _packed_avals(capacity, width, mtype_slots)
    rows = []
    k = ring_depth
    with chip_side_tracing():
        args1 = _abstract((tables, state, bi, bf), one)
        if "packed_step" in programs:
            t0 = time.perf_counter()
            rows.append(_report(
                "packed_step",
                build_packed_step(donate=True).lower(*args1), t0))
        if "packed_chain" in programs:
            t0 = time.perf_counter()
            rows.append(_report(
                f"packed_chain_k{k}_donated",
                build_packed_chain(k, donate=True).lower(
                    args1[0], args1[1], *([args1[2]] * k),
                    *([args1[3]] * k)), t0))
        if "sharded_chain" in programs:
            m_tables = jax.tree.map(
                lambda a, spec: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
                tables, _packed_tables_specs())
            m_state = _abstract(
                state, NamedSharding(mesh, _PACKED_STATE_SPEC))
            m_bi, m_bf = _abstract(
                (bi, bf), NamedSharding(mesh, _PACKED_BATCH_SPEC))
            t0 = time.perf_counter()
            rows.append(_report(
                f"sharded_chain_k{k}_2x2",
                build_sharded_packed_chain(mesh, k, donate=True).lower(
                    m_tables, m_state, *([m_bi] * k), *([m_bf] * k)), t0))
        if "geo_pallas" in programs:
            b, z, v = pallas_shape
            pts = jax.ShapeDtypeStruct((b, 2), jnp.float32, sharding=one)
            verts = jax.ShapeDtypeStruct((z, v, 2), jnp.float32,
                                         sharding=one)
            t0 = time.perf_counter()
            rows.append(_report(
                f"geo_pallas_{b}x{z}x{v}",
                points_in_polygons_pallas.lower(
                    pts, verts, interpret=False), t0))
    return rows


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--capacity", type=int, default=4096)
    p.add_argument("--width", type=int, default=1024)
    p.add_argument("--ring-depth", type=int, default=8)
    p.add_argument("--pallas", type=int, nargs=3, default=(4096, 100, 8),
                   metavar=("B", "Z", "V"))
    args = p.parse_args()
    aot_check(args.capacity, args.width, args.ring_depth,
              pallas_shape=tuple(args.pallas))


if __name__ == "__main__":
    main()

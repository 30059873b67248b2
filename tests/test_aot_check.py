"""The chip side of the device programs compiles, with no chip present.

``tools/aot_check.py`` lowers against the v5e topology libtpu describes
and traces with ``jax.default_backend()`` answering ``"tpu"``, so a
change that breaks the TPU side of a backend switch fails HERE, in
tier-1, instead of on the first chip run.
"""

import os
import sys

import jax
import pytest

pytest.importorskip("libtpu")

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import aot_check  # noqa: E402


def test_chip_side_programs_compile_for_v5e(capsys):
    rows = {r["program"]: r for r in aot_check.aot_check(
        capacity=4096, width=1024, ring_depth=8,
        pallas_shape=(4096, 100, 8))}
    assert set(rows) == {"packed_step", "packed_chain_k8_donated",
                         "sharded_chain_k8_2x2", "geo_pallas_4096x100x8"}
    # memory_analysis() is printed for every program
    printed = capsys.readouterr().out
    assert printed.count('"temp_bytes"') == 4
    for row in rows.values():
        assert row["argument_bytes"] > 0 and row["output_bytes"] > 0
    # the donated carry is aliased input -> output, not copied: the
    # chain's and, since PR 36, the single step's
    assert rows["packed_chain_k8_donated"]["alias_bytes"] > 0
    assert (rows["packed_step"]["alias_bytes"]
            == rows["packed_chain_k8_donated"]["alias_bytes"])
    # K steps, ONE collective: the psum of the stacked metrics block
    assert rows["sharded_chain_k8_2x2"]["collectives"] == {"all-reduce": 1}
    assert rows["packed_chain_k8_donated"]["collectives"] == {}
    # per-chip state on the 2x2 mesh is a quarter of the one-chip carry
    assert (rows["sharded_chain_k8_2x2"]["alias_bytes"] * 4
            == rows["packed_chain_k8_donated"]["alias_bytes"])


@pytest.mark.parametrize("capacity", [1 << 14, 1 << 18])
def test_chip_side_step_costs_the_batch_not_the_registry(capacity):
    """The chip's own compile of the packed step and of the donated
    chain, at one width and two capacities: nothing sized by the registry
    but the carry (scattered into in place and never copied: the step
    is handed the epoch under the state manager's lock and donates it,
    as the chain donates its lease), the registry table and the
    ``present_now`` vector."""
    rows = {r["program"]: r for r in aot_check.aot_check(
        capacity=capacity, width=32, ring_depth=8,
        programs=("packed_step", "packed_chain"))}
    aot_check.assert_step_costs_the_batch(
        rows["packed_step"]["hlo"], capacity, donated=True)
    aot_check.assert_step_costs_the_batch(
        rows["packed_chain_k8_donated"]["hlo"], capacity, donated=True)
    # no scatter was expanded into a serial loop over the batch: the one
    # while of the chain is its own fori_loop
    assert rows["packed_step"]["hlo"].count(" while(") == 0
    assert rows["packed_chain_k8_donated"]["hlo"].count(" while(") == 1


@pytest.mark.parametrize("width, capacity", [(65536, 1 << 20),
                                             (16384, 1 << 18)])
def test_chip_side_narrow_rung_costs_the_batch(width, capacity):
    """The single step as a partial plan rides it (PR 33): lowered at
    the narrowest rung of a deployment's width — 1,024 columns at the
    shipped 65,536 against the shipped 1<<20 slots — the chip's compile
    holds nothing sized by the registry but the carry, updated in place
    with no copy of it (PR 36: the 1.63 ms of a 1.85 ms step), the
    registry table and ``present_now``, and expands no scatter."""
    from sitewhere_tpu.ingest.batcher import plan_rungs
    from sitewhere_tpu.pipeline.packed import packed_row_width

    (row,) = aot_check.aot_check(
        capacity=capacity, width=plan_rungs(width)[0],
        programs=("packed_step",))
    aot_check.assert_step_costs_the_batch(row["hlo"], capacity,
                                          donated=True)
    assert row["hlo"].count(" while(") == 0
    # the whole carry is aliased input -> output
    assert row["alias_bytes"] == 4 * capacity * packed_row_width(8, 3)


def test_chip_side_tracing_is_scoped():
    assert jax.default_backend() == "cpu"
    with aot_check.chip_side_tracing():
        assert jax.default_backend() == "tpu"
    assert jax.default_backend() == "cpu"

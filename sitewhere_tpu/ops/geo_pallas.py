"""Tiled Pallas point-in-polygon kernel for large zone sets.

The dense :func:`sitewhere_tpu.ops.geo.points_in_polygons` materializes a
``[B, Z, V]`` crossing tensor; fine for the pipeline's default zone table
(Z ≤ a few hundred) but at large B·Z·V that intermediate dominates HBM
traffic.  This kernel tiles the ``[B, Z]`` output grid, streams each
polygon tile's edges through VMEM once, and accumulates crossing parity
over vertices — the working set per grid cell is ``TB·TZ`` ints plus one
``TZ``-wide edge slice, independent of V.

Mosaic constraints found on real hardware (v5e, 2026-07-29): edges must be
vertex-major ``[V, Z]`` so the per-vertex slice is a dynamic *sublane*
index (a dynamic lane-axis column load fails to legalize), and crossing
parity must be carried as int32 (i1 vectors fail to legalize as loop
carries).  The vertex loop is UNROLLED (V is small and static) and each
edge's inverse slope is precomputed outside the kernel, removing the
per-iteration divide — together 2.2x over the fori_loop/divide form
(measured on v5e at B=131072, Z=512, V=16: 2.9 ms vs 6.4 ms).

Same padding contract as the dense path (repeat-last-vertex, wraparound
edge equals closing edge).  Reference behavior mirrored:
``service-rule-processing/.../geospatial/ZoneTestRuleProcessor.java:32-70``
(JTS ``contains`` per event × zone).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# [B, Z] output tile: sublane × lane aligned for float32/bool VPU ops.
TILE_B = 512
TILE_Z = 128


def _pip_kernel(px_ref, py_ref, y1_ref, y2_ref, x1_ref, slope_ref, out_ref):
    """One [TB, TZ] tile: parity of edge crossings over all V vertices.

    ``slope_ref[v] = (x2 - x1) / (y2 - y1)`` (guarded against horizontal
    edges, which never straddle) so the crossing abscissa is one fused
    multiply-add per vertex.
    """
    px = px_ref[:]  # [TB, 1]
    py = py_ref[:]
    n_verts = y1_ref.shape[0]

    parity = jnp.zeros(out_ref.shape, jnp.int32)
    for v in range(n_verts):  # static unroll: V is small (padded ring)
        y1 = y1_ref[pl.ds(v, 1), :]  # [1, TZ]
        y2 = y2_ref[pl.ds(v, 1), :]
        x1 = x1_ref[pl.ds(v, 1), :]
        slope = slope_ref[pl.ds(v, 1), :]
        straddles = (y1 > py) != (y2 > py)
        x_cross = slope * (py - y1) + x1
        crossing = straddles & (px < x_cross)
        # Carry parity as int32: Mosaic cannot legalize i1 vectors as
        # loop carries, and xor-int is as cheap as xor-bool on the VPU.
        parity = parity ^ crossing.astype(jnp.int32)
    out_ref[:] = parity.astype(jnp.bool_)


@functools.partial(jax.jit, static_argnames=("interpret",))
def points_in_polygons_pallas(
    points: jax.Array, verts: jax.Array, interpret: bool = False
) -> jax.Array:
    """Drop-in for :func:`points_in_polygons` via the tiled kernel.

    Args:
      points: ``float32[B, 2]`` (x, y).
      verts:  ``float32[Z, V, 2]`` padded rings.
      interpret: run in interpreter mode (CPU tests).

    Returns ``bool[B, Z]``.
    """
    b, _ = points.shape
    z, v, _ = verts.shape
    pad_b = (-b) % TILE_B
    pad_z = (-z) % TILE_Z

    # Lay out points as [B, 1] columns (sublane-major) and polygon edges
    # vertex-major as [V, Z] (zones ride the lane axis; the kernel's dynamic
    # per-vertex slice rides the sublane axis); pad Z with degenerate
    # polygons (zero area -> no crossings).
    px = jnp.pad(points[:, 0], (0, pad_b)).reshape(-1, 1)
    py = jnp.pad(points[:, 1], (0, pad_b)).reshape(-1, 1)
    x1 = jnp.pad(verts[:, :, 0], ((0, pad_z), (0, 0))).T  # [V, Zp]
    y1 = jnp.pad(verts[:, :, 1], ((0, pad_z), (0, 0))).T
    x2 = jnp.roll(x1, -1, axis=0)
    y2 = jnp.roll(y1, -1, axis=0)
    # Horizontal edges (y2 == y1) never straddle; the guard only keeps the
    # division finite.
    denom = jnp.where(y2 == y1, 1.0, y2 - y1)
    slope = (x2 - x1) / denom

    bp, zp = b + pad_b, z + pad_z
    grid = (bp // TILE_B, zp // TILE_Z)
    edge_spec = lambda: pl.BlockSpec(  # noqa: E731 — six identical specs
        (v, TILE_Z), lambda i, j: (0, j), memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _pip_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_B, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_B, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            edge_spec(), edge_spec(), edge_spec(), edge_spec(),
        ],
        out_specs=pl.BlockSpec((TILE_B, TILE_Z), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bp, zp), jnp.bool_),
        interpret=interpret,
    )(px, py, y1, y2, x1, slope)
    return out[:b, :z]


# Dense-vs-Pallas crossover, from an earlier v5e session (2026-07-30,
# fetch-forced timing; not re-measured since): at B=131072, V=16 the
# dense path won at Z=64 (0.47 ms vs 0.91 ms — XLA's fused [B,Z,V]
# pipeline beats the kernel while the intermediate still fits) and lost
# at Z=512 (3.34 ms vs 2.87 ms).
PALLAS_WORK_THRESHOLD = 1 << 29

PALLAS_ENABLED = bool(int(os.environ.get("SW_TPU_GEO_PALLAS", "1")))


def points_in_polygons_auto(points: jax.Array, verts: jax.Array) -> jax.Array:
    """Pick dense XLA vs tiled Pallas by static work size + backend."""
    from sitewhere_tpu.ops.geo import points_in_polygons

    b = points.shape[0]
    z, v, _ = verts.shape
    if (PALLAS_ENABLED and jax.default_backend() == "tpu"
            and b * z * v >= PALLAS_WORK_THRESHOLD):
        return points_in_polygons_pallas(points, verts)
    return points_in_polygons(points, verts)

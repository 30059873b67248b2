"""``shard_map`` with the replication check off — ONE import site.

Every SPMD builder in this repo (pipeline/sharded.py,
analytics/runner.py) imports from HERE: the local bodies use
psum/ppermute with explicitly replicated outputs the checker cannot
always prove, so ``check_vma`` defaults to False in one place.
"""

from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check_vma)


__all__ = ["shard_map"]

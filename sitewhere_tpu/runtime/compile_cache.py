"""Where JAX keeps compiled programs between processes.

At the shipped pipeline defaults the packed step and the K-step chain
each take about a minute to compile, and a process that finds them in
JAX's persistent compilation cache loads them in seconds instead.  The
cache directory is part of the cache key's lookup, so it has to be the
same path on every run:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets nothing — whoever deploys the program places the
  cache.
- unset: ``<checkout>/.jax_cache`` (git-ignored), next to the package.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed place before
    the first compile.  Idempotent; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

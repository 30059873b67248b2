"""Rehearsals run on the CPU with 4 virtual devices (the mesh cell) and
no persistent compile cache, whatever the shell has set."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

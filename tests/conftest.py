"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference has almost no CI-runnable tests (SURVEY.md §4 — live-instance
drivers against a hard-coded host).  We instead run the full SPMD program on
a forced-CPU JAX backend with 8 virtual devices so multi-chip sharding logic
is exercised on every test run without TPU hardware.
"""

import os

# Must be set before jax initializes its backends.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Tests always run on the CPU backend, whatever JAX_PLATFORMS says (must
# happen before any backend is initialized).
jax.config.update("jax_platforms", "cpu")
# Hermetic: toy programs compile in well under a second, and a test must
# never pass on an executable some earlier run left in <checkout>/.jax_cache
# (Instance points the persistent cache there; see runtime/compile_cache.py).
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

import time_limit  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from sitewhere_tpu.parallel.mesh import make_mesh

    return make_mesh(n_devices=8)


def pytest_configure(config):
    # every test's setup, call and teardown runs under a time limit
    config.pluginmanager.register(time_limit, "time_limit")
    config.addinivalue_line(
        "markers", "slow: long-running soak / multi-process integration tests")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (runtime.faults) — "
        "tier-1, NOT slow: failure paths must be proven on every run")

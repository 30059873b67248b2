"""The compile cache's place is decided outside the program, or is
``<checkout>/.jax_cache`` — never a temp dir, a pid or a timestamp."""

import os

import jax
import pytest

from sitewhere_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_left_to_jax(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == "/x"
    # nothing set in code: JAX reads the variable itself at start-up
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want   # idempotent
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_instance_enables_it_before_its_first_compile(monkeypatch, tmp_path,
                                                      restore_cache_dir):
    from sitewhere_tpu.instance import Instance
    from sitewhere_tpu.runtime.config import Config

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    inst = Instance(Config({
        "instance": {"id": "cc", "data_dir": str(tmp_path)},
        "pipeline": {"width": 64, "registry_capacity": 256},
    }, apply_env=False))
    try:
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        inst.terminate()

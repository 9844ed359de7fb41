"""utils/compile_cache.py: the compile cache is placed from outside."""

import os

import jax

from distributed_inference_engine_tpu.utils import compile_cache


def test_exported_dir_is_left_alone(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR exported the helper reports that
    directory and sets nothing in code (jax reads the variable itself)."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_in_checkout_dir(monkeypatch):
    """Unset: one fixed git-ignored directory at the root of the checkout
    (the path is part of the cache key — no temp name, pid or time), and
    the suite's decision to keep the cache OFF (tests/conftest.py) stands."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.configure_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_cache")
        assert compile_cache.configure_compile_cache() == got
        assert jax.config.jax_compilation_cache_dir == got
        assert jax.config.jax_enable_compilation_cache is False
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

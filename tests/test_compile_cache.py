"""Placement of the persistent compilation cache by the entry points."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_jax_own_setting(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_unset_env_uses_fixed_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == got
    # the same path on every call: the directory is part of the cache key
    assert compile_cache.enable_compile_cache() == got

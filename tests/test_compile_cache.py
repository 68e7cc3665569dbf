"""Persistent compile cache placement (repro.launch.compile_cache)."""

import pytest

import jax

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_stands(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(monkeypatch, tmp_path,
                                           cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = tmp_path / ".jax_cache"
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", fixed)
    assert compile_cache.enable_compile_cache() == str(fixed)
    assert compile_cache.enable_compile_cache() == str(fixed)   # stable
    assert fixed.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(fixed)


def test_default_dir_is_repo_root():
    root = compile_cache.DEFAULT_CACHE_DIR.parent
    assert compile_cache.DEFAULT_CACHE_DIR.name == ".jax_cache"
    assert (root / "src" / "repro").is_dir()

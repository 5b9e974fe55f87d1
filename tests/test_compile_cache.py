"""Where the persistent compilation cache lands (launch/compile_cache.py)."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_cache_dir_is_env_or_fixed_checkout_dir(monkeypatch, tmp_path, restore_cache_dir, env_dir):
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        # fixed: the same checkout always gets the same directory
        assert compile_cache.enable_compile_cache() == got
        assert compile_cache.REPO_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets nothing
        assert jax.config.jax_compilation_cache_dir == before

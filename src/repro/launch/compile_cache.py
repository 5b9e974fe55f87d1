"""Where JAX keeps its persistent compilation cache.

A chip run compiles the grouped kernel once per shape bucket; a second
process on the same checkout should find those programs again.  JAX
keys the cache by its directory, so the directory must not move between
runs: it is ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), else ``.jax_cache`` at the root of
the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)

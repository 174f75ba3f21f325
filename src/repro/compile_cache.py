"""JAX persistent compilation cache, shared by every entry point.

Each entry point calls :func:`enable_compile_cache` before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and nothing here overrides it.  Otherwise the cache
lives at the fixed ``<checkout>/.jax_cache`` (listed in .gitignore):
the cache path is part of each entry's key, so a path that moved from
run to run (a temp dir, a pid or a timestamp) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path

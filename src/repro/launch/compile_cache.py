"""JAX's persistent compilation cache, placed from outside.

Entry points call :func:`enable_compile_cache` once, before their first
compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, because the path is part of
the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

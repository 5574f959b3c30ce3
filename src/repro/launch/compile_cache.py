"""JAX's persistent compilation cache for the launch entry points.

Call :func:`enable_compile_cache` first, before anything compiles.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this sets
nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed path,
because the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path

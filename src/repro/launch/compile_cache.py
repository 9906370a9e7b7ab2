"""JAX's persistent compilation cache, kept in one fixed directory.

Called by the entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.launch.solve_serve``), never at library import.  The
directory is part of a cache entry's key, so it must not move between runs:
where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
set here; otherwise the cache lives in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

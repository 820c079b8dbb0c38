"""JAX persistent compilation cache placement.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives at a fixed ``<repo>/.jax_cache``:
the directory is part of the cache key, so a temporary, per-process or
timestamped path would never hit. Entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/run.py``) call ``enable_compile_cache``
once, before their first compile.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turns the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

"""JAX's persistent compilation cache for the repo's entry points.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``) call
:func:`enable_compile_cache` once at start-up; library modules and tests
never do. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing else is set. Otherwise the cache lives at a fixed path in the
checkout — the path is part of the cache's key, so it must not move.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)

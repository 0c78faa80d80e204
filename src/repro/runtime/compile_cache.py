"""Persistent XLA compilation cache for the program's entry points.

Called by ``chip_smoke.py``, ``repro.launch.serve.main`` and
``benchmarks.run`` — never at import.  When ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here.  Otherwise the cache
lives at a fixed ``<checkout>/.jax_cache``: the directory is part of what
a later process must find again, so it is never a temp, pid or time path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

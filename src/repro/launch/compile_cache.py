"""JAX's persistent compilation cache for the entry points.

Call :func:`enable_compile_cache` from an entry point's ``main`` (serving
CLI, benchmark CLI, ``chip_smoke.py``), never at import: importing the
library must not change process-wide JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# repository root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to the fixed path
    ``<checkout>/.jax_cache``: the path is part of each entry's key, so a
    directory that moved between runs would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

"""JAX's persistent compilation cache, placed where the deployment says.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call :func:`enable_compile_cache` before their first compile; nothing calls
it at import, so the tests compile without a persistent cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (gitignored): the directory is part of what makes
an entry found again, so it never comes from a temporary name, a process id
or the clock.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the dispatch kernel and the decode step compile in well under the
    # 1 s default threshold; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path

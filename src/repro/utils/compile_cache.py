"""JAX's persistent compilation cache, placed where the deployment says.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call :func:`enable_compile_cache` before their first compile; nothing calls
it at import, so the tests compile without a persistent cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (gitignored): the directory is part of what makes
an entry found again, so it never comes from a temporary name, a process id
or the clock.

:func:`compile_count` counts the process's backend compiles as they happen,
from JAX's own monitoring events.
"""

from __future__ import annotations

import dataclasses
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


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class CompileCount:
    """Running totals of this process's backend compiles: ``compiles`` and
    their ``seconds`` (a program loaded from the persistent cache counts as
    one, timed by its retrieval), and ``cache_hits``, the loads among them."""

    compiles: int = 0
    seconds: float = 0.0
    cache_hits: int = 0

    def _on_duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


_COUNT: CompileCount | None = None


def compile_count() -> CompileCount:
    """The process's one :class:`CompileCount`.  JAX's monitoring listeners
    are process-wide and cannot be taken off again, so there is one count
    per process, its listeners registered at the first call (never at
    import); compiles before that call are not in it.  Read a difference of
    two readings to count the compiles of a stretch of code."""
    global _COUNT
    if _COUNT is None:
        _COUNT = CompileCount()
        jax.monitoring.register_event_duration_secs_listener(_COUNT._on_duration)
        jax.monitoring.register_event_listener(_COUNT._on_event)
    return _COUNT

"""Compile and persistent-cache events from ``jax.monitoring``."""
from __future__ import annotations

import threading
import time
from typing import List, Tuple

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileLog:
    """Every executable JAX obtains (compiled, or read from the persistent
    cache) with the host time it arrived, plus persistent-cache hits and
    misses.  The listeners stay registered for the life of the process."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles: List[Tuple[float, float, str]] = []  # (t, secs, fun)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.compiles.append((time.perf_counter(), duration,
                                      str(kw.get("fun_name", "?"))))

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1
        elif event == CACHE_MISS:
            self.cache_misses += 1

    @property
    def count(self) -> int:
        return len(self.compiles)

    @property
    def seconds(self) -> float:
        return sum(c[1] for c in self.compiles)

    def between(self, t0: float, t1: float) -> List[Tuple[float, float, str]]:
        """Executables that arrived in ``[t0, t1]`` (``time.perf_counter``)."""
        with self._lock:
            return [c for c in self.compiles if t0 <= c[0] <= t1]

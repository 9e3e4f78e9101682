"""Compilations this process made, from ``jax.monitoring`` (a copy of the
arithmetic of ``easydl_tpu/utils/profiling.CompileWatch``, kept here so the
program cannot change what the benchmark counts).

``seconds`` sums every backend compile *or* persistent-cache retrieval (the
event wraps both); ``events`` counts them, so the difference of two readings
around a window is the number of programs that were compiled, or fetched
from the cache, inside it. Construct before the first compile."""

from __future__ import annotations


class CompileWatch:
    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.events = 0
        self.saved = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.events += 1
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved += seconds

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

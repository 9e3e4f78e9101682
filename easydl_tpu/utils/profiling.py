"""Profiling hooks: XLA traces and compile counters (SURVEY.md §5.1).

The reference promises performance monitoring (README.md:21-23) with no
mechanism; the coarse per-step pipeline here is
:class:`easydl_tpu.core.metrics.MetricsRecorder` → Brain. This module is the
deep-dive layer on top: ``jax.profiler`` device traces viewable in
TensorBoard/Perfetto (compute/communication overlap, HBM, per-op time).
``Trainer.train_step`` annotates itself (``train_step`` with its step
number, ``easydl/shard_batch``, ``easydl/dispatch``), so a trace taken here
has its steps marked; the reduction from a trace to numbers lives with the
benchmark (``benchmark/lib``).

Usage::

    with trace("/tmp/profile"):          # whole-region trace
        for step in range(10):
            state, m = trainer.train_step(state, batch)

    prof = StepProfiler("/tmp/profile", start_step=5, num_steps=3)
    for step in range(20):
        prof.maybe_start(step)           # traces only steps [5, 8)
        ...
        prof.maybe_stop(step)
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax

from easydl_tpu.utils.logging import get_logger

log = get_logger("utils", "profiling")


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture an XLA device trace for the enclosed region."""
    jax.profiler.start_trace(logdir)
    log.info("profiler trace started -> %s", logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written -> %s", logdir)


class CompileWatch:
    """What this process has spent compiling, from ``jax.monitoring``.

    ``seconds`` sums every XLA compile *or* persistent-cache retrieval (the
    backend-compile event wraps both), so the same counter read in a cold
    process and in one that found the cache gives the cold and the warm
    compile time; ``saved`` sums what the cache's answers would have cost to
    compile (each entry records it); ``hits``/``misses`` count them.
    Before the backend is asked at all, a program is traced to a jaxpr
    (``trace_seconds``) and lowered to MLIR (``lower_seconds``) — paid by a
    cached program too; ``retrieval_seconds`` is the part of ``seconds``
    spent fetching from the persistent cache. A jitted function traced
    inside another reports its own duration and is inside the outer one's
    as well: ``trace_seconds`` is the time covered by any trace (each event
    ends when it is reported and began its duration earlier), not the sum.
    Construct before the first compile."""

    #: jax.monitoring duration event -> the attribute that sums it
    _DURATIONS = {
        "/jax/core/compile/backend_compile_duration": "seconds",
        "/jax/compilation_cache/compile_time_saved_sec": "saved",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
        "/jax/compilation_cache/cache_retrieval_time_sec":
            "retrieval_seconds",
    }

    def __init__(self) -> None:
        self.seconds = 0.0
        self.saved = 0.0
        self.trace_seconds = 0.0
        self.lower_seconds = 0.0
        self.retrieval_seconds = 0.0
        self.hits = 0
        self.misses = 0
        self._traces: list = []  # disjoint [start, end] of traces, in order
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        attr = self._DURATIONS.get(event)
        if attr is not None:
            setattr(self, attr, getattr(self, attr) + seconds)
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            end = time.perf_counter()
            start = end - seconds
            # inner traces were reported before the one that holds them
            while self._traces and self._traces[-1][1] > start:
                inner = self._traces.pop()
                self.trace_seconds -= inner[1] - inner[0]
                start = min(start, inner[0])
            self._traces.append((start, end))
            self.trace_seconds += end - start

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> dict:
        return {"compile_s": round(self.seconds, 3),
                "cache_saved_s": round(self.saved, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}

    def totals(self) -> dict:
        """The running totals under the names the elastic timeline carries
        them (``first_step_done``); a caller that wants a stretch's share
        subtracts two readings (:meth:`since`)."""
        return {"trace_s": self.trace_seconds, "lower_s": self.lower_seconds,
                "backend_s": self.seconds,
                "cache_retrieval_s": self.retrieval_seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def since(self, earlier: dict) -> dict:
        """``totals()`` less an ``earlier`` reading, seconds to the ms."""
        return {k: round(v - earlier[k], 3)  # an int stays an int
                for k, v in self.totals().items()}


def peak_device_bytes() -> Optional[int]:
    """Largest ``peak_bytes_in_use`` over this process's devices, or None
    where the backend keeps no memory statistics (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class StepProfiler:
    """Window-triggered tracing inside a training loop: skips compile/warmup
    steps and captures exactly ``num_steps`` steady-state steps."""

    def __init__(self, logdir: str, start_step: int = 5, num_steps: int = 3):
        self.logdir = logdir
        self.start_step = start_step
        self.stop_step = start_step + num_steps
        self._active = False
        self._done = False

    def maybe_start(self, step: int) -> None:
        if not self._done and not self._active and step >= self.start_step:
            jax.profiler.start_trace(self.logdir)
            self._active = True
            log.info("profiling steps [%d, %d) -> %s", step, self.stop_step,
                     self.logdir)

    def maybe_stop(self, step: int) -> None:
        if self._active and step + 1 >= self.stop_step:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True

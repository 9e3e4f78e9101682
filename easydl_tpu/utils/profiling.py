"""Profiling hooks: XLA traces and compile counters (SURVEY.md §5.1).

The reference promises performance monitoring (README.md:21-23) with no
mechanism; the coarse per-step pipeline here is
:class:`easydl_tpu.core.metrics.MetricsRecorder` → Brain. This module is the
deep-dive layer on top: ``jax.profiler`` device traces viewable in
TensorBoard/Perfetto (compute/communication overlap, HBM, per-op time).
``Trainer.train_step`` annotates itself (``train_step`` with its step
number, ``easydl/shard_batch``, ``easydl/dispatch``) and the elastic worker
the rest of its loop (:func:`host_span`), so a trace taken here has its
steps marked; the reduction from a trace to numbers lives with the
benchmark (``benchmark/lib``).

Usage::

    prof = StepProfiler("/tmp/profile", start_step=5, num_steps=3)
    for step in range(20):
        prof.maybe_start(step)           # traces only steps [5, 8)
        ...
        prof.maybe_stop(step)
"""

from __future__ import annotations

import glob
import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

from easydl_tpu.utils.logging import get_logger

log = get_logger("utils", "profiling")


class host_span(jax.profiler.TraceAnnotation):
    """A host span that keeps its own length: a
    ``jax.profiler.TraceAnnotation`` (free without a profiler session, a
    span on the device trace's clock inside one) whose ``seconds``
    (``perf_counter``) are there to read once the block is left."""

    seconds = 0.0

    def __enter__(self):
        self._entered = time.perf_counter()
        return super().__enter__()

    def __exit__(self, *exc):
        out = super().__exit__(*exc)
        self.seconds = time.perf_counter() - self._entered
        return out


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

    The same seconds by program (:meth:`table`): jax names the function
    with every duration (``fun_name``: ``train_step`` when it traces,
    ``jit(train_step)`` when it lowers and compiles; one row for both). A
    trace inside another's is the outer program's, as in ``trace_seconds``;
    a fetch from the persistent cache carries no name and is the row's
    whose backend event ends next (the fetch happens inside it). Each
    column of the table sums to its total. Construct before the first
    compile. (jax reports each duration as a time span too; a listener is
    called at the event's end, so the end is read here and one listener
    serves.)"""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
    #: jax.monitoring duration event -> the attribute that sums it
    _DURATIONS = {
        _BACKEND: "seconds",
        "/jax/compilation_cache/compile_time_saved_sec": "saved",
        _LOWER: "lower_seconds",
        _RETRIEVAL: "retrieval_seconds",
    }
    #: ... -> its column of the by-program table (tracing's is folded first)
    _COLUMN = {_BACKEND: "backend_s", _LOWER: "lower_s"}
    #: a row of the table
    COLUMNS = ("trace_s", "lower_s", "backend_s", "cache_retrieval_s")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.saved = 0.0
        self.trace_seconds = 0.0
        self.lower_seconds = 0.0
        self.retrieval_seconds = 0.0
        self.hits = 0
        self.misses = 0
        # disjoint (start, end, program) of traces, in order
        self._traces: List[Tuple[float, float, str]] = []
        self._rows: Dict[str, Dict[str, float]] = {}  # all but trace_s
        self._spans: List[Tuple[float, float, str]] = []  # of the others
        self._unnamed_retrieval = 0.0  # fetched, its backend event to come
        # an event's ends are taken on perf_counter, as a trace's always
        # were; the table gives them on the timeline's clock
        self._wall = time.time() - time.perf_counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @staticmethod
    def _program(fun_name: str) -> str:
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            return fun_name[len("jit("):-1]
        return fun_name

    def _duration(self, event: str, seconds: float, fun_name: str = "",
                  **_) -> None:
        attr = self._DURATIONS.get(event)
        if attr is not None:
            setattr(self, attr, getattr(self, attr) + seconds)
        if event == self._RETRIEVAL:
            self._unnamed_retrieval += seconds
        elif event in self._COLUMN:
            program = self._program(fun_name)
            row = self._rows.setdefault(
                program, dict.fromkeys(self.COLUMNS[1:], 0.0))
            row[self._COLUMN[event]] += seconds
            if event == self._BACKEND:
                row["cache_retrieval_s"] += self._unnamed_retrieval
                self._unnamed_retrieval = 0.0
            end = time.perf_counter()
            self._spans.append((end - seconds, end, program))
        elif event == self._TRACE:
            end = time.perf_counter()
            start = end - seconds
            # inner traces were reported before the one that holds them
            while self._traces and self._traces[-1][1] > start:
                inner = self._traces.pop()
                self.trace_seconds -= inner[1] - inner[0]
                start = min(start, inner[0])
            self._traces.append((start, end, self._program(fun_name)))
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

    def table(self) -> Dict[str, Dict[str, float]]:
        """The running seconds by program: ``{program: {trace_s, lower_s,
        backend_s, cache_retrieval_s, start, end}}``, ``start`` and ``end``
        the first start and the last end of any of its events, in
        ``time.time()``."""
        out = {name: dict(row, trace_s=0.0)
               for name, row in self._rows.items()}
        for start, end, name in self._traces:
            row = out.setdefault(name, dict.fromkeys(self.COLUMNS, 0.0))
            row["trace_s"] += end - start
        for start, end, name in self._traces + self._spans:
            row = out[name]
            row["start"] = min(row.get("start", start), start)
            row["end"] = max(row.get("end", end), end)
        for row in out.values():
            row["start"] += self._wall
            row["end"] += self._wall
        return out

    def table_since(self, earlier: Dict[str, Dict[str, float]]
                    ) -> Dict[str, Dict[str, float]]:
        """``table()`` less an ``earlier`` reading: the programs that spent
        anything since, each with what it spent; ``start`` only for a
        program the earlier reading did not have."""
        out = {}
        for name, row in self.table().items():
            before = earlier.get(name)
            spent = {c: row[c] - before[c] if before else row[c]
                     for c in self.COLUMNS}
            if any(spent.values()):
                out[name] = dict(spent, end=row["end"],
                                 start=None if before else row["start"])
        return out


def largest_programs(table: Dict[str, Dict[str, float]], n: int = 5
                     ) -> dict:
    """A compile table as a timeline record carries it: ``programs``, the
    ``n`` rows with the most seconds (tracing, lowering and the backend;
    the cache's fetches are inside the backend's), largest first, each with
    its ``start`` and ``end`` on the timeline's clock where the table has
    them, and ``other_programs_s``, the seconds of the rest. To the ms."""
    def seconds(row):
        return row["trace_s"] + row["lower_s"] + row["backend_s"]

    rows = sorted(table.items(), key=lambda kv: -seconds(kv[1]))
    return {
        "programs": [
            dict({"name": name}, **{
                c: round(row[c], 3) for c in CompileWatch.COLUMNS + (
                    "start", "end") if row.get(c) is not None})
            for name, row in rows[:n]],
        "other_programs_s": round(
            sum(seconds(row) for _, row in rows[n:]), 3),
    }


def peak_device_bytes() -> Optional[int]:
    """Largest ``peak_bytes_in_use`` over this process's devices, or None
    where the backend keeps no memory statistics (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class StepProfiler:
    """Window-triggered tracing inside a training loop: skips compile/warmup
    steps and captures exactly ``num_steps`` steady-state steps.
    ``options`` is a ``jax.profiler.ProfileOptions`` for the session (the
    elastic worker turns the Python tracer off, as the benchmark does: its
    host spans are the program's own)."""

    def __init__(self, logdir: str, start_step: int = 5, num_steps: int = 3,
                 options: Optional[jax.profiler.ProfileOptions] = None):
        self.logdir = logdir
        self.start_step = start_step
        self.stop_step = start_step + num_steps
        self.options = options
        self._active = False
        self._done = False

    def maybe_start(self, step: int) -> None:
        if not self._done and not self._active and step >= self.start_step:
            jax.profiler.start_trace(self.logdir,
                                     profiler_options=self.options)
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


def newest_xplane(logdir: str) -> Optional[str]:
    """The ``.xplane.pb`` of the last session written under ``logdir``."""
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


class RequestedProfile:
    """A profile window that a running loop is asked for (the elastic
    worker's ``SIGUSR2``): ``start(step)`` at a step boundary opens a
    :class:`StepProfiler` session — Python tracer off — for the number of
    steps and into the directory the optional request file names
    (``{"steps": n, "dir": path}``, read once and removed; 4 steps into
    ``default_dir(step)`` without it); ``step_done(step)`` after each step
    closes it once the window's steps have run; ``close()`` on the loop's way out.

    ``on_event(name, **data)`` is told ``profile_started`` (``t``, ``step``
    — the boundary, steps done so far —, ``steps``, ``dir``) right after the
    session is up and ``profile_written`` (``seconds`` the session took to
    end and write, ``path`` and ``bytes`` of the ``.xplane.pb``,
    ``first_step`` and ``last_step`` as the step records number them) when
    it is down. Inside the session sits one ``easydl/clock`` annotation
    whose ``unix_s`` is ``profile_started``'s ``t``: the offset between
    ``time.time()`` and the trace's clock, in both files. A request while a
    window is open is dropped; a session that cannot start or end is logged
    and the loop goes on."""

    def __init__(self, request_path: str,
                 default_dir: Callable[[int], str],
                 on_event: Callable[..., None]):
        self.request_path = request_path
        self._default_dir = default_dir
        self._on_event = on_event
        self._profiler: Optional[StepProfiler] = None
        self._first_step = self._last_step = 0

    def _request(self, step: int) -> Tuple[int, str]:
        steps, logdir = 4, self._default_dir(step)
        try:
            with open(self.request_path) as f:
                request = json.load(f)
            os.remove(self.request_path)
            steps = max(1, int(request.get("steps", steps)))
            logdir = str(request.get("dir") or logdir)
        except FileNotFoundError:
            pass
        except (OSError, ValueError, TypeError, AttributeError) as e:
            log.warning("profile request %s unreadable (%r): defaults",
                        self.request_path, e)
        return steps, logdir

    def start(self, step: int) -> None:
        if self._profiler is not None:
            log.warning("profile request at step %d dropped: the window "
                        "from step %d is still open", step, self._first_step)
            return
        steps, logdir = self._request(step)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        profiler = StepProfiler(logdir, start_step=step, num_steps=steps,
                                options=options)
        try:
            profiler.maybe_start(step)
        except Exception:  # a session someone else holds, a bad directory
            log.exception("profile request at step %d: no session", step)
            return
        unix_s = time.time()
        with jax.profiler.TraceAnnotation("easydl/clock", unix_s=unix_s):
            pass
        self._profiler = profiler
        self._first_step, self._last_step = step + 1, step
        self._on_event("profile_started", t=unix_s, step=step, steps=steps,
                       dir=logdir)

    def step_done(self, step: int) -> None:
        """Step ``step`` (as its record numbers it: steps done so far) has
        run and its record is written."""
        if self._profiler is not None:
            self._last_step = step
            if step >= self._profiler.stop_step:
                self.close()

    def close(self) -> None:
        profiler, self._profiler = self._profiler, None
        if profiler is None:
            return
        t0 = time.perf_counter()
        try:
            profiler.close()
        except Exception:
            log.exception("profile window from step %d: the session did "
                          "not end cleanly", self._first_step)
        seconds = time.perf_counter() - t0
        path = newest_xplane(profiler.logdir)
        self._on_event(
            "profile_written", seconds=round(seconds, 3), path=path,
            bytes=os.path.getsize(path) if path else 0,
            first_step=self._first_step, last_step=self._last_step)

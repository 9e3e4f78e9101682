"""Profiling hooks: XLA traces + step annotations (SURVEY.md §5.1).

The reference promises performance monitoring (README.md:21-23) with no
mechanism; the coarse per-step pipeline here is
:class:`easydl_tpu.core.metrics.MetricsRecorder` → Brain. This module is the
deep-dive layer on top: ``jax.profiler`` device traces viewable in
TensorBoard/Perfetto (compute/communication overlap, HBM, per-op time) and
named step/phase annotations that show up inside those traces.

Usage::

    with trace("/tmp/profile"):          # whole-region trace
        for step in range(10):
            with step_annotation("train", step):
                state, m = trainer.train_step(state, batch)

    prof = StepProfiler("/tmp/profile", start_step=5, num_steps=3)
    for step in range(20):
        prof.maybe_start(step)           # traces only steps [5, 8)
        ...
        prof.maybe_stop(step)
"""

from __future__ import annotations

import contextlib
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


def step_annotation(name: str, step: Optional[int] = None):
    """Label the enclosed work in the trace timeline (StepTraceAnnotation
    when a step number is given, else a named TraceAnnotation)."""
    if step is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=step)
    return jax.profiler.TraceAnnotation(name)


class CompileWatch:
    """What this process has spent compiling, from ``jax.monitoring``.

    ``seconds`` sums every XLA compile *or* persistent-cache retrieval (the
    backend-compile event wraps both), so the same counter read in a cold
    process and in one that found the cache gives the cold and the warm
    compile time; ``saved`` sums what the cache's answers would have cost to
    compile (each entry records it); ``hits``/``misses`` count them.
    Construct before the first compile."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.saved = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved += seconds

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> dict:
        return {"compile_s": round(self.seconds, 3),
                "cache_saved_s": round(self.saved, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}


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


# --------------------------------------------------------------- attribution
#
# Chrome-trace parsing for scripts/bench_profile.py. Split out here (pure
# stdlib, no jax at call time) so the attribution logic is unit-testable
# against synthetic traces — the round-4 artifact was internally
# incoherent precisely because the parser ran only against real traces it
# could misread (umbrella events double-counted, while-bodies opaque,
# busy > span so the "gap" went to -184%).


def categorize_op(name: str, args: Optional[dict] = None) -> str:
    """Category for one DEVICE op event.

    The specific name signal wins over the profiler's generic hlo
    category: flash-attention kernels ARE custom calls and the profiler
    tags them so — letting a 'custom' category preempt the name check
    would re-create the r4 symptom (flash attributed ~0, lumped into
    custom_call). Generic categories then refine whatever the name
    doesn't pin down."""
    n = name.lower()
    if "flash" in n:
        return "flash_attention"
    if args:
        for key in ("hlo_category", "category"):
            cat = str(args.get(key, "")).lower()
            if cat:
                if "convolution" in cat or "dot" in cat or "gemm" in cat:
                    return "matmul"
                if "custom" in cat:
                    return "custom_call"
                if "all-reduce" in cat or "all-gather" in cat \
                        or "collective" in cat or "reduce-scatter" in cat:
                    return "collectives"
    if "custom-call" in n or "custom_call" in n:
        return "custom_call"
    if ("all-reduce" in n or "all-gather" in n or "reduce-scatter" in n
            or "collective" in n or "ppermute" in n or "all-to-all" in n):
        return "collectives"
    if n.startswith(("dot", "convolution")) or "gemm" in n or "einsum" in n:
        return "matmul"
    if "dynamic-update-slice" in n or "dynamic_update_slice" in n:
        return "dus_carry"
    if "fusion" in n:
        # XLA fuses elementwise chains into the producing/consuming op;
        # matmul-rooted fusions usually keep 'dot' in the name
        if "dot" in n or "conv" in n:
            return "matmul_fusion"
        if "dynamic-update-slice" in n or "dus" in n:
            return "dus_carry"
        if "reduce" in n:
            return "reduction_fusion"
        return "other_fusion"
    if "infeed" in n or "outfeed" in n or "copy" in n or "transpose" in n:
        return "data_movement"
    if "scan" in n or n.startswith("while") or "conditional" in n:
        return "control_flow"
    return "other"


#: Event names that are wrappers around real device work — a jit program,
#: a module, a named step region. Their SELF time (gaps not covered by any
#: child op) is reported as "unattributed_parent", never as op work.
_UMBRELLA_MARKERS = ("jit_", "jit(", "module", "program", "xlamodule")


def _is_umbrella(name: str) -> bool:
    n = name.lower()
    return n.startswith(_UMBRELLA_MARKERS) or n in ("train_step", "step")


def _self_times(events):
    """Self time per event for one lane of Chrome X events.

    Events may nest (a fusion inside a while inside a jit program); the
    Chrome format encodes nesting purely by interval containment on the
    same (pid, tid). Sorting by (ts, -dur) and keeping a stack of open
    intervals yields each event's direct parent; a child's duration is
    subtracted from its parent so every microsecond is attributed exactly
    once. Returns [(name, self_us, had_children)]."""
    evs = sorted(events, key=lambda e: (e["ts"], -e["dur"]))
    out = []
    stack = []  # indices into out; [(end_ts, out_idx)]
    for e in evs:
        ts, dur = e["ts"], e["dur"]
        while stack and ts >= stack[-1][0] - 1e-9:
            stack.pop()
        out_idx = len(out)
        out.append([e["name"], dur, False, e.get("args") or {}])
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= dur
            parent[2] = True
        stack.append((ts + dur, out_idx))
    return [(n, max(s, 0.0), c, a) for n, s, c, a in out]


def _union_us(events) -> float:
    """Total covered time of a lane — union of [ts, ts+dur), overlap-safe
    (nested events must not inflate 'busy' past the wall span)."""
    iv = sorted((e["ts"], e["ts"] + e["dur"]) for e in events)
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in iv:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def attribute_trace(trace_doc: dict, top: int = 15) -> dict:
    """Attribute device time from one Chrome-trace document.

    Picks the busiest DEVICE ops lane (thread named like 'XLA Ops' under a
    TPU/device process; falls back to the busiest thread of any device
    process), computes per-op SELF time (children subtracted), categorizes
    leaves, and reports invariants instead of trusting itself:

    - categories (incl. unattributed_parent) sum to the lane's busy time;
    - busy is an interval union, so gap_pct ∈ [0, 100].
    """
    events = trace_doc.get("traceEvents", [])
    pid_names, tid_names = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pid_names[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            tid_names[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", ""))
    device_pids = {
        pid for pid, label in pid_names.items()
        if "tpu" in label.lower() or "/device" in label.lower()
        or "gpu" in label.lower()
    }
    if not device_pids:
        device_pids = set(pid_names) or {
            e.get("pid") for e in events if e.get("ph") == "X"}

    lanes = {}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        key = (e["pid"], e.get("tid"))
        lanes.setdefault(key, []).append({
            "name": e.get("name", "?"),
            "ts": float(e.get("ts", 0.0)),
            "dur": float(e.get("dur", 0.0)),
            "args": e.get("args"),
        })
    if not lanes:
        return {"error": "no device X events in trace"}

    ops_lanes = [
        k for k in lanes if "xla ops" in tid_names.get(k, "").lower()
    ]
    candidates = ops_lanes or list(lanes)
    busiest = max(candidates, key=lambda k: _union_us(lanes[k]))
    lane = lanes[busiest]

    selfs = _self_times(lane)
    cats: dict = {}
    per_op: dict = {}
    for name, self_us, had_children, args in selfs:
        if _is_umbrella(name):
            # wrapper self-time = device time no leaf op covers
            cats["unattributed_parent"] = (
                cats.get("unattributed_parent", 0.0) + self_us)
            continue
        # Non-umbrella parents (a while op around its body, a fused region
        # around sub-ops) keep their own SELF time under their own category
        # — that's genuine loop/dispatch overhead, not their children's work.
        cat = categorize_op(name, args)
        cats[cat] = cats.get(cat, 0.0) + self_us
        per_op[name] = per_op.get(name, 0.0) + self_us

    busy_us = _union_us(lane)
    span = (min(e["ts"] for e in lane),
            max(e["ts"] + e["dur"] for e in lane))
    span_us = span[1] - span[0]
    cat_sum = sum(cats.values())
    gap_pct = 100.0 * (1.0 - busy_us / span_us) if span_us else 0.0
    invariants = {
        "categories_sum_us": round(cat_sum, 1),
        "lane_busy_us": round(busy_us, 1),
        "categories_cover_busy": bool(
            busy_us == 0 or abs(cat_sum - busy_us) / busy_us < 0.02),
        "gap_pct_in_range": bool(-1e-6 <= gap_pct <= 100.0),
    }
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "lane": f"{pid_names.get(busiest[0], busiest[0])}"
                f" / {tid_names.get(busiest, busiest[1])}",
        "ops_lane_count": len(ops_lanes),
        "lane_busy_us": round(busy_us, 1),
        "lane_span_us": round(span_us, 1),
        "lane_gap_pct": round(gap_pct, 2),
        "category_self_us": {
            k: round(v, 1)
            for k, v in sorted(cats.items(), key=lambda kv: -kv[1])
        },
        "top_ops_self_us": [
            {"op": name[:120], "us": round(dur, 1),
             "pct_of_busy": round(100 * dur / busy_us, 2) if busy_us else 0.0}
            for name, dur in top_ops
        ],
        "invariants": invariants,
    }

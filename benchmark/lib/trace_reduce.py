"""From a profiler trace to numbers: device busy and idle time, time by
operation, collectives exposed or hidden, idle gaps by what the host did.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but jax (``jax.profiler.ProfileData``), into a plain structure:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds each device plane's "XLA Ops" line — one event per executed
HLO instruction; events may nest (a ``while`` around its body). The chip's
trace names an event by the instruction's whole text (``%fusion.123 =
bf16[...] fusion(...)``); here it is cut to the instruction's name
(``fusion.123``, ``tpu_custom_call.70``, ``all-gather-start.3``), which is
what the compiled program's own HLO calls it. An asynchronous operation (a
copy, a collective XLA made asynchronous) is two events there, ``x-start.N``
and ``x-done.N``; ``async_spans`` pairs them into one span from start to
done. (The trace's "Async XLA Ops" line holds the same spans — checked on the
chip: 11,924 of 11,924 collective-permutes agree to the nanosecond — but only
for the first device, so it is not read.) ``host`` holds the benchmark's own
``TraceAnnotation``s (names starting with ``bench/``) from the host plane.
All are on one clock (seen on the chip: the host's window annotation opens
0.9 ms before the first device operation). Everything below
works on that structure, so it is tested on a recorded fixture and on
synthetic traces. The interval arithmetic (self time by containment, busy as
a union) is copied from ``easydl_tpu/utils/profiling.attribute_trace``.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Sequence[Any]  # [name, start_ns, dur_ns]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "bench/"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|async-collective)")
_ASYNC_END = re.compile(r"^(.*)-(start|done)((?:\.\d+)?)$")


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[List[Any]]] = {}
    host: List[List[Any]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[8]{0} fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def describe_xplane(path: str, sample: int = 5) -> Dict[str, Any]:
    """Planes, lines, event counts and a few event names with their stats —
    what to look at by hand before trusting a reducer."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = {
                "events": len(events),
                "sample": [[e.name, e.start_ns, e.duration_ns,
                            {k: str(v)[:80] for k, v in e.stats}]
                           for e in events[:sample]]}
        out[plane.name] = lines
    return out


# ------------------------------------------------------------- intervals
def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Disjoint, sorted cover of ``[lo, hi)`` intervals."""
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def covered(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(hi - lo for lo, hi in union(intervals))


def clip(events: Iterable[Event], lo: float, hi: float) -> List[List[Any]]:
    """Events cut to the window ``[lo, hi)``; those outside it dropped."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def self_times(events: Iterable[Event]) -> List[Tuple[str, float]]:
    """``(name, self_ns)`` per event of one line: its duration less what its
    direct children cover. Nesting is interval containment on the line, so
    sorting by (start, -duration) with a stack of open intervals finds each
    event's parent, and every nanosecond is counted once."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []
    for name, start, dur in ordered:
        while stack and start >= stack[-1][0] - 1e-9:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        stack.append((start + dur, len(out)))
        out.append([name, dur])
    return [(name, max(ns, 0.0)) for name, ns in out]


def window_of(trace: Dict[str, Any], name: str = "bench/window"
              ) -> Optional[Tuple[float, float]]:
    """The traced window: the host annotation ``name`` where the trace has
    it, else from the first device operation's start to the last one's end."""
    for ev_name, start, dur in trace["host"]:
        if ev_name == name:
            return start, start + dur
    spans = [(e[1], e[1] + e[2]) for evs in trace["devices"].values()
             for e in evs]
    if not spans:
        return None
    return min(lo for lo, _ in spans), max(hi for _, hi in spans)


# --------------------------------------------------------------- numbers
def busy(trace: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """``busy_s``: seconds in which an operation ran on the device, union of
    the operation intervals inside the window, averaged over the devices;
    ``window_s``: the window's length. None where no operation ran."""
    window = window_of(trace)
    if window is None or not trace["devices"]:
        return None
    per_device = [covered((s, s + d) for _, s, d in clip(evs, *window))
                  for evs in trace["devices"].values()]
    if not any(per_device):
        return None
    return {"busy_s": sum(per_device) / len(per_device) / 1e9,
            "window_s": (window[1] - window[0]) / 1e9}


def time_by_op(trace: Dict[str, Any]) -> Dict[str, float]:
    """Self seconds by operation name, summed over the window and averaged
    over the devices."""
    window = window_of(trace)
    totals: Dict[str, float] = {}
    if window is None:
        return totals
    n = max(len(trace["devices"]), 1)
    for evs in trace["devices"].values():
        for name, ns in self_times(clip(evs, *window)):
            totals[name] = totals.get(name, 0.0) + ns / 1e9 / n
    return totals


def ops_by_name(trace: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """``{name: {"seconds", "calls"}}``: how long each operation ran in the
    window (its whole duration: what a reader asks about, a kernel, has no
    children) and how often, averaged over the devices."""
    window = window_of(trace)
    out: Dict[str, Dict[str, float]] = {}
    if window is None:
        return out
    n = max(len(trace["devices"]), 1)
    for evs in trace["devices"].values():
        for name, _, dur in clip(evs, *window):
            row = out.setdefault(name, {"seconds": 0.0, "calls": 0.0})
            row["seconds"] += dur / 1e9 / n
            row["calls"] += 1.0 / n
    return out


def async_spans(events: Iterable[Event]) -> List[List[Any]]:
    """``[name of the start, start_ns, dur_ns]`` for every ``x-start.N``
    event with a later ``x-done.N``: from the start's beginning to the
    done's end."""
    began: Dict[Tuple[str, str], Tuple[str, float]] = {}
    spans = []
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        m = _ASYNC_END.match(name)
        if not m:
            continue
        key = (m.group(1), m.group(3))
        if m.group(2) == "start":
            began[key] = (name, start)
        elif key in began:
            first, t0 = began.pop(key)
            spans.append([first, t0, start + dur - t0])
    return spans


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name.lstrip("%")))


def collectives(trace: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """``collective_s``: seconds a collective operation was running — the
    synchronous ones and the asynchronous ones from start to done
    (``async_spans``); ``exposed_s``: the part of them during which no other operation
    ran on that device. Averaged over the devices. Leaf operations only: a
    ``while`` that contains collectives is neither."""
    window = window_of(trace)
    if window is None or not trace["devices"]:
        return None
    total = exposed = 0.0
    for evs in trace["devices"].values():
        evs = clip(evs, *window)
        parents = _parents(evs)
        coll = union((s, s + d) for nm, s, d in evs + async_spans(evs)
                     if is_collective(nm))
        other = union((s, s + d) for i, (nm, s, d) in enumerate(evs)
                      if not is_collective(nm) and i not in parents)
        total += sum(hi - lo for lo, hi in coll)
        exposed += sum(hi - lo for lo, hi in coll) - _overlap(coll, other)
    n = len(trace["devices"])
    return {"collective_s": total / n / 1e9, "exposed_s": exposed / n / 1e9}


def _parents(events: Sequence[Event]) -> set:
    """Indices of events that contain another event of the line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    parents, stack = set(), []
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and start >= stack[-1][0] - 1e-9:
            stack.pop()
        if stack:
            parents.add(stack[-1][1])
        stack.append((end, i))
    return parents


def _overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two disjoint sorted covers."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(trace: Dict[str, Any], top: int = 10) -> List[List[Any]]:
    """Idle time of the first device inside the window by what the host was
    doing: each gap between device operations is shared out among the host
    annotations (``bench/...``, the window itself left out) that overlap it,
    the rest going to ``"host: unannotated"``. ``[[name, seconds], ...]``,
    largest first."""
    window = window_of(trace)
    if window is None or not trace["devices"]:
        return []
    first = trace["devices"][sorted(trace["devices"])[0]]
    cover = union((s, s + d) for _, s, d in clip(first, *window))
    edges = [window[0]] + [x for lo, hi in cover for x in (lo, hi)] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [e for e in trace["host"] if e[0] != "bench/window"]
    by_name: Dict[str, float] = {}
    for lo, hi in gaps:
        left = hi - lo
        for name, s, d in spans:
            ns = min(hi, s + d) - max(lo, s)
            if ns > 0:
                by_name[name] = by_name.get(name, 0.0) + ns / 1e9
                left -= ns
        if left > 0:
            by_name["host: unannotated"] = by_name.get(
                "host: unannotated", 0.0) + left / 1e9
    return [[k, v] for k, v in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:top]]


def summarise(trace: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """Everything the readers and the result line take from a trace, reduced
    once: ``busy_s``, ``window_s``, ``collective_s``, ``exposed_s``, ``ops``
    (``ops_by_name``), ``top_ops`` (self seconds, largest first) and
    ``idle_gaps``. None where no operation ran on a device."""
    busy_ = busy(trace)
    if busy_ is None:
        return None
    by_self = sorted(time_by_op(trace).items(), key=lambda kv: -kv[1])
    return {**busy_, **collectives(trace), "ops": ops_by_name(trace),
            "top_ops": [[k, v] for k, v in by_self[:top]],
            "idle_gaps": idle_gaps(trace, top)}

"""Per-host phase timeline for recovery/reshape decomposition.

The reference promises fast elastic recovery (README.md:25-35) without a
mechanism; our generation switch has seven distinct phases (quiesce consensus,
drain checkpoint, re-rendezvous, process spawn, runtime imports, distributed
init, restore, first-step compile) and optimizing the wrong one is easy —
round 2's compile cache bought ~10s of a ~60s stall because process start,
not recompile, dominated. Every worker/agent appends one JSON line per phase
boundary to ``timeline-<agent>.jsonl`` in the job workdir; the master's
``events.jsonl`` carries the plan/phase transitions. ``scripts/
measure_recovery.py`` folds both into the per-phase breakdown in
RECOVERY.json.

Records: ``{"t": <unix time>, "phase": str, "gen": int, ...}``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List

# In-process listeners: fn(path, record) called on every emit. One
# instrumentation point feeds both the JSONL decomposition AND live gauges —
# the agent bridges its phase boundaries into /metrics by registering here
# (easydl_tpu/elastic/agent.py), so the two views can never drift apart.
# Listeners fire only in the emitting process; a worker subprocess' emits
# reach other processes through the JSONL file, as before.
_listeners: List[Callable[[str, Dict[str, Any]], None]] = []
_listeners_lock = threading.Lock()


_listener_errors = None  # lazy: keep the obs import off worker start


def _count_listener_error() -> None:
    """A raising listener is swallowed (the emit contract) but must not be
    INVISIBLE: a broken timeline→metrics bridge silently loses the whole
    phase decomposition. Best-effort — counting can never raise either."""
    global _listener_errors
    try:
        if _listener_errors is None:
            from easydl_tpu.obs import get_registry

            _listener_errors = get_registry().counter(
                "easydl_timeline_listener_errors_total",
                "Timeline listener callbacks that raised (exception "
                "swallowed; the phase bridge is degraded).",
            )
        _listener_errors.inc()
    except Exception:
        pass


def add_listener(fn: Callable[[str, Dict[str, Any]], None]) -> None:
    with _listeners_lock:
        _listeners.append(fn)


def remove_listener(fn: Callable[[str, Dict[str, Any]], None]) -> None:
    with _listeners_lock:
        try:
            _listeners.remove(fn)
        except ValueError:
            pass


def emit(path: str | None, phase: str, generation: int, /,
         **data: Any) -> None:
    """Append one phase boundary; never raises (timing is best-effort and
    must not take down a worker). The three arguments are positional only:
    a phase's data may use their names (``profile_written`` has a ``path``)."""
    if not path:
        return
    rec = {"t": time.time(), "phase": phase, "gen": int(generation), **data}
    with _listeners_lock:
        listeners = list(_listeners)
    for fn in listeners:
        try:
            fn(path, rec)
        except Exception:
            # Same contract as the file write: never raises — but counted,
            # so a broken bridge shows in /metrics instead of silently
            # losing phase→gauge data.
            _count_listener_error()
    try:
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass


def read(path: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    try:
        with open(path) as f:
            for line in f:
                if line.strip():
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue  # torn concurrent append
    except OSError:
        pass
    return out


def read_all(workdir: str) -> List[Dict[str, Any]]:
    """All agents' timelines in one list (unsorted; callers filter by gen)."""
    out: List[Dict[str, Any]] = []
    try:
        names = os.listdir(workdir)
    except OSError:
        return out
    for name in names:
        if name.startswith("timeline-") and name.endswith(".jsonl"):
            for rec in read(os.path.join(workdir, name)):
                rec["source"] = name[len("timeline-"):-len(".jsonl")]
                out.append(rec)
    return out

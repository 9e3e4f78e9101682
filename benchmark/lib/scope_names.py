"""Device time under names of the program's own, beside
``lib/scope_reduce.py``'s fixed table of parts: the same trace, the same
paths and the same self times, filtered by name here.

A share is over the busy time of the traced window, every pass (forward,
backward, recomputed), each operation counted once. Where the program that
ran has no operation under the names — a program from before a scope
existed — there is nothing to report, and the readers return None rather
than 0.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Iterable, Optional

from lib import scope_reduce, trace_reduce


@functools.lru_cache(maxsize=1)
def _self_seconds(path: str, mtime: float) -> Dict[str, float]:
    return trace_reduce.time_by_op(trace_reduce.load_xplane(path))


def seconds_under(artifacts: Dict[str, Any], every: Iterable[str],
                  some: Iterable[str]) -> Optional[float]:
    """Self seconds, in the traced window, of the operations whose path
    holds every name of ``every`` and one of ``some``; None where the run
    has no trace with whole paths or no operation's path does."""
    found = scope_reduce.of_run(artifacts)
    if not found or not found["whole_paths"]:
        return None
    every, some = set(every), set(some)
    ops = []
    for op, path in found["paths"].items():
        names = set(scope_reduce.names_on(path)[1])
        if every <= names and some & names:
            ops.append(op)
    if not ops:
        return None
    path = scope_reduce.trace_file()
    seconds = _self_seconds(path, os.path.getmtime(path))
    return sum(seconds.get(op, 0.0) for op in ops)


def pct_under(artifacts: Dict[str, Any], every: Iterable[str],
              some: Iterable[str]) -> Optional[float]:
    """``seconds_under`` as a share of the window's busy time."""
    seconds = seconds_under(artifacts, every, some)
    if seconds is None:
        return None
    return 100.0 * seconds / scope_reduce.of_run(artifacts)["total_s"]


def pct_under_any(artifacts: Dict[str, Any], names: Iterable[str]
                  ) -> Optional[float]:
    """Share of the busy time of the operations whose path holds any of
    ``names``."""
    return pct_under(artifacts, (), names)

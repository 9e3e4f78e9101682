"""The ``goodput`` phase of an elastic run's timeline (PR 47): the account
the agent keeps of its own chip-seconds (``easydl_tpu/elastic/goodput.py``),
a snapshot on the timeline after every commit, restore and first step and
once when the agent stops.

The window of the kill-resume cell opens at C0's commit, which emits one
(its ``t`` is the commit's, so it may precede the driver's ``t_open`` by its
20 ms poll), and closes just before the agent's ``stop()``, which emits the
last. The readers difference the two snapshots nearest those edges and
divide by THEIR interval, not the window's. Nothing where a run has no such
phase (a program from before PR 47, a steady cell) or a snapshot lies more
than ``EDGE_S`` from its edge.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

Snapshot = Dict[str, Any]

EDGE_S = 2.0


def edges(artifacts: Dict[str, Any]) -> Optional[Tuple[Snapshot, Snapshot]]:
    """The snapshots nearest ``t_open`` and ``t_close``."""
    snaps = [e for e in artifacts.get("timeline", ())
             if e.get("phase") == "goodput"]
    if not snaps or artifacts.get("t_open") is None:
        return None
    pair = []
    for edge in (artifacts["t_open"], artifacts["t_close"]):
        snap = min(snaps, key=lambda e: abs(e["t"] - edge))
        if abs(snap["t"] - edge) > EDGE_S:
            return None
        pair.append(snap)
    return (pair[0], pair[1]) if pair[1]["t"] > pair[0]["t"] else None


def over_window(artifacts: Dict[str, Any],
                amount: Callable[[Snapshot], float]) -> Optional[float]:
    """``amount`` at the closing snapshot less ``amount`` at the opening."""
    pair = edges(artifacts)
    return amount(pair[1]) - amount(pair[0]) if pair else None


def share_pct(artifacts: Dict[str, Any],
              amount: Callable[[Snapshot], float]) -> Optional[float]:
    """``over_window`` as a share of the two snapshots' own interval."""
    pair = edges(artifacts)
    if pair is None:
        return None
    return 100.0 * (amount(pair[1]) - amount(pair[0])) / (
        pair[1]["t"] - pair[0]["t"])

"""The ``goodput`` phase of an elastic run's timeline (PR 47): the account
the agent keeps of its own chip-seconds (``easydl_tpu/elastic/goodput.py``),
a snapshot on the timeline after every commit, restore and first step and
once when the agent stops.

The window of the kill-resume cell opens at C0's commit, which emits one,
and closes just before the agent's ``stop()``, which emits the last. A
snapshot's ``t`` is the newest line the agent had been FED when it took it,
not the time it was written: the opening one's is the commit's own (so it
may precede the driver's ``t_open`` by its 20 ms poll) or a step record's
behind it, the closing one's the last step record's — up to a step before
``t_close``, and seconds where the resumed generation has not stepped yet. So
the readers take the snapshots by what they ARE, not by how near an edge
their ``t`` lies (a frozen host can push the closing one seconds from its
edge): the OPENING one is the first at or after the ``ckpt_committed`` of the
run's first save, the CLOSING one the run's last; they are differenced and divided by THEIR interval, not
the window's, and how far each stands from its edge is kept among the
artifacts (``goodput_edges``: ``edge_distances``). Nothing where a run has no
such phase (a program from before PR 47, a steady cell), no commit of its
first save on the timeline, or no snapshot behind the opening one.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

Snapshot = Dict[str, Any]


def edges(artifacts: Dict[str, Any]) -> Optional[Tuple[Snapshot, Snapshot]]:
    """The snapshot that opens the window and the one that closes it."""
    timeline = artifacts.get("timeline") or ()
    saves = artifacts.get("save_steps") or (None,)
    commit = next((e for e in timeline if e.get("phase") == "ckpt_committed"
                   and e.get("step") == saves[0]), None)
    snaps = [e for e in timeline if e.get("phase") == "goodput"]
    if commit is None or not snaps:
        return None
    opening = next((s for s in snaps if s["t"] >= commit["t"]), None)
    closing = snaps[-1]
    if opening is None or closing["t"] <= opening["t"]:
        return None
    return opening, closing


def edge_distances(artifacts: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Seconds from the window's edges to the two snapshots' ``t`` (negative:
    before the edge). Kept among the artifacts; decides nothing."""
    pair = edges(artifacts)
    if pair is None or artifacts.get("t_open") is None:
        return None
    return {"opening_after_t_open_s": pair[0]["t"] - artifacts["t_open"],
            "closing_after_t_close_s": pair[1]["t"] - artifacts["t_close"]}


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

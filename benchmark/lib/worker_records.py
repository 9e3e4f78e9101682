"""What the elastic worker's step records say of a step's inside, reduced
over the window's pace pairs (``timeline_reduce.pace_pairs``: consecutive
records of one generation, the save's and the kill's intervals left out).

Since the worker times its own loop, a record carries beside ``step_time_s``
where that time went — ``data_s`` (the wait for the next batch), ``shard_s``
and ``dispatch_s`` (placing the batch, handing the step to the runtime),
``wait_s`` (the blocking fetch of the step's numbers: the device) — the
``gap_s`` between the previous step's fetch and this step's start, and
``commit_in_flight``. Records from before a field existed have no such key,
and every function here then gives None."""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence

from lib import phase_records, timeline_reduce as tl


def window_pairs(artifacts: Dict[str, Any]) -> List[tuple]:
    if "records" not in artifacts:
        return []
    return tl.pace_pairs(artifacts["records"], artifacts["t_open"],
                         artifacts["t_close"], artifacts["save_steps"])


def pace_share_pct(artifacts: Dict[str, Any], fields: Sequence[str]
                   ) -> Optional[float]:
    """100 x the sum of ``fields`` over the pairs' later records, over the
    sum of the pairs' intervals: the four shares (input wait, dispatch,
    device wait, gap) are parts of one whole and add up to 100."""
    pairs = window_pairs(artifacts)
    if not pairs or any(f not in b for _, b in pairs for f in fields):
        return None
    return 100.0 * sum(b[f] for _, b in pairs for f in fields) / sum(
        b["t"] - a["t"] for a, b in pairs)


def commit_drag_pct(artifacts: Dict[str, Any]) -> Optional[float]:
    """How much slower the loop runs beside an asynchronous commit: the
    median interval of the pairs whose later step began with a save's
    chunks still being written, over the median of the others, less 1."""
    pairs = window_pairs(artifacts)
    if any("commit_in_flight" not in b for _, b in pairs):
        return None
    under = [b["t"] - a["t"] for a, b in pairs if b["commit_in_flight"]]
    beside = [b["t"] - a["t"] for a, b in pairs if not b["commit_in_flight"]]
    if not under or not beside:
        return None
    return 100.0 * (statistics.median(under) / statistics.median(beside) - 1)


def resume_programs(artifacts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``{"rows": {program: row}, "other_s": seconds}`` from the resuming
    generation's ``first_step_done``: what it traced, lowered and loaded
    between ``restored`` and its first step's end, by program."""
    rec = phase_records.of_resume(artifacts, "first_step_done")
    if rec is None or "programs" not in rec:
        return None
    return {"rows": {row["name"]: row for row in rec["programs"]},
            "other_s": rec.get("other_programs_s", 0.0)}

"""From the elastic worker's step records and phase timeline to numbers.

Inputs are plain lists of dicts, as ``metrics-<agent>.jsonl`` (``step``,
``loss``, ``step_time_s``, ``generation``, wall ``t``) and
``timeline-<agent>.jsonl`` (``t``, ``phase``, ``gen``) hold them, plus the
facts the driver took itself on the same clock (``time.time()``): when the
window opened and closed, when it sent the SIGKILL, which steps saved, and
when each checkpoint's commit marker appeared. Pure functions: tested on a
recorded pair and on made-up ones.
"""

from __future__ import annotations

import json
import statistics
from typing import Any, Dict, Iterable, List, Optional

Record = Dict[str, Any]


def read_jsonl(path: str) -> List[Record]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue  # a line torn by the kill
    except OSError:
        pass
    return out


def in_window(records: Iterable[Record], t_open: float,
              t_close: float) -> List[Record]:
    return [r for r in records if t_open <= r["t"] <= t_close]


def pace_pairs(records: List[Record], t_open: float, t_close: float,
               save_steps: Iterable[int]) -> List[tuple]:
    """``(earlier, later)`` pairs of consecutive step records inside the
    window that show the loop's own pace: same generation, consecutive step
    numbers, and the earlier one not a saving step (that interval holds the
    save's stall). The pair across the kill is of two generations, so it is
    out as well."""
    saves = set(save_steps)
    recs = in_window(records, t_open, t_close)
    return [(a, b) for a, b in zip(recs, recs[1:])
            if a["generation"] == b["generation"]
            and b["step"] == a["step"] + 1 and a["step"] not in saves]


def step_interval_s(records, t_open, t_close, save_steps) -> Optional[float]:
    """Median seconds between consecutive step records (see ``pace_pairs``)."""
    gaps = [b["t"] - a["t"]
            for a, b in pace_pairs(records, t_open, t_close, save_steps)]
    return statistics.median(gaps) if gaps else None


def window_steps_per_s(records: List[Record], t_open: float,
                       t_close: float) -> Optional[float]:
    """All the steps over all the time of a window that ONE generation
    fills: from the record that opened the window (the newest at or before
    ``t_open``; the window's first where there is none) to the window's last
    record, the steps between the two over the seconds between them — a
    save's stall, a compile or any other wait among them. None where the
    two records are of different generations: a kill lies between them."""
    inside = in_window(records, t_open, t_close)
    before = [r for r in records if r["t"] < t_open]
    if not inside:
        return None
    first = max(before, key=lambda r: r["t"]) if before else inside[0]
    last = inside[-1]
    if first["generation"] != last["generation"] or last["t"] <= first["t"]:
        return None
    return (last["step"] - first["step"]) / (last["t"] - first["t"])


def loop_overhead_pct(records, t_open, t_close, save_steps) -> Optional[float]:
    """Share of the worker loop's pace that is not the step itself:
    ``1 - median step_time_s / median interval``, over the same pairs."""
    pairs = pace_pairs(records, t_open, t_close, save_steps)
    if not pairs:
        return None
    interval = statistics.median(b["t"] - a["t"] for a, b in pairs)
    step = statistics.median(b["step_time_s"] for _, b in pairs)
    return 100.0 * (1.0 - step / interval)


def save_stall_s(records: List[Record], save_step: int) -> Optional[float]:
    """How long the step loop stood still at the save after ``save_step``:
    the time from that step's record to the next one's, less the next
    step's own time. The first generation that reached the step counts."""
    by_step = {}
    for r in records:
        by_step.setdefault((r["generation"], r["step"]), r)
    for (gen, step), rec in sorted(by_step.items()):
        nxt = by_step.get((gen, step + 1))
        if step == save_step and nxt is not None:
            return (nxt["t"] - rec["t"]) - nxt["step_time_s"]
    return None


def first_record_after(records: List[Record], generation: int
                       ) -> Optional[Record]:
    later = [r for r in records if r["generation"] > generation]
    return min(later, key=lambda r: r["t"]) if later else None


def resume_s(records: List[Record], t_kill: float,
             killed_generation: int) -> Optional[float]:
    """Seconds from the SIGKILL to the first step record of a later
    generation."""
    rec = first_record_after(records, killed_generation)
    return rec["t"] - t_kill if rec else None


def generations(timeline: List[Record]) -> List[int]:
    """Generations that were spawned, in order."""
    return sorted({e["gen"] for e in timeline if e["phase"] == "spawn"})


def extra_generations(timeline: List[Record], expected: int = 2) -> int:
    return max(len(generations(timeline)) - expected, 0)


def phase_t(timeline: List[Record], phase: str, gen: int) -> Optional[float]:
    """Time of the first ``phase`` event of generation ``gen``."""
    ts = [e["t"] for e in timeline if e["phase"] == phase and e["gen"] == gen]
    return min(ts) if ts else None


def phase_span_s(timeline: List[Record], gen: int, start: str,
                 end: str) -> Optional[float]:
    a, b = phase_t(timeline, start, gen), phase_t(timeline, end, gen)
    return b - a if a is not None and b is not None else None


def resume_span_s(artifacts: Dict[str, Any], start: Optional[str],
                  end: str) -> Optional[float]:
    """Seconds between two timeline phases of the generation that resumed
    after the kill, from an elastic run's artifacts; from the kill itself
    where ``start`` is None. None where there was no kill or no resume."""
    if artifacts.get("t_kill") is None:
        return None
    gen = resuming_generation(artifacts["records"],
                              artifacts["killed_generation"])
    if gen is None:
        return None
    if start is None:
        t_end = phase_t(artifacts["timeline"], end, gen)
        return t_end - artifacts["t_kill"] if t_end is not None else None
    return phase_span_s(artifacts["timeline"], gen, start, end)


def resuming_generation(records: List[Record], killed_generation: int
                        ) -> Optional[int]:
    rec = first_record_after(records, killed_generation)
    return rec["generation"] if rec else None


def commit_s(records: List[Record], commits: Dict[str, float],
             step: int) -> Optional[float]:
    """Seconds from the record of saving step ``step`` to the appearance of
    that checkpoint's commit marker."""
    t_commit = commits.get(str(step))
    recs = [r for r in records if r["step"] == step]
    if t_commit is None or not recs:
        return None
    return t_commit - min(r["t"] for r in recs)

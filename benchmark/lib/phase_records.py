"""Whole records of the elastic timeline, where a reader needs the data a
phase carries and not only its time (``timeline_reduce`` gives the times):
the compile counters on ``first_step_done``, the seconds and bytes on the
checkpoint's ``ckpt_*`` phases, ``directive_t`` on ``spawn``. A timeline from
before a phase existed has no such record, and the reader gets None."""

from __future__ import annotations

from typing import Any, Dict, Optional

from lib import timeline_reduce as tl

Record = Dict[str, Any]


def first(timeline, phase: str, **equal: Any) -> Optional[Record]:
    """The earliest record of ``phase`` whose fields equal ``equal``."""
    found = [e for e in timeline if e["phase"] == phase
             and all(e.get(k) == v for k, v in equal.items())]
    return min(found, key=lambda e: e["t"]) if found else None


def reap_s(artifacts: Dict[str, Any]) -> Optional[float]:
    """Seconds from the driver's SIGKILL to the agent's ``worker_crash`` of
    the killed generation."""
    if artifacts.get("t_kill") is None:
        return None
    crash = tl.phase_t(artifacts["timeline"], "worker_crash",
                       artifacts["killed_generation"])
    return crash - artifacts["t_kill"] if crash is not None else None


def of_resume(artifacts: Dict[str, Any], phase: str) -> Optional[Record]:
    """The ``phase`` record of the generation that resumed after the kill."""
    if artifacts.get("t_kill") is None:
        return None
    gen = tl.resuming_generation(artifacts["records"],
                                 artifacts["killed_generation"])
    if gen is None:
        return None
    return first(artifacts["timeline"], phase, gen=gen)


def of_save(artifacts: Dict[str, Any], phase: str, which: int
            ) -> Optional[Record]:
    """The ``phase`` record of the run's ``which``-th periodic save (0: C0 at
    step N, the one that commits; -1: S1 at 2N, the one the kill beats), from
    the generation that first reached that step."""
    if "timeline" not in artifacts or not artifacts.get("save_steps"):
        return None
    return first(artifacts["timeline"], phase,
                 step=artifacts["save_steps"][which])

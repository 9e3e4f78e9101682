"""What a job's chip-seconds went to, counted by the job itself while it runs.

An :class:`Account` is fed the lines of ONE agent's two files as they appear
— the worker's step records (``metrics-<agent>.jsonl``) and the timeline the
agent and its worker share (``timeline-<agent>.jsonl``) — and holds, since
the agent's first ``spawn``, cumulative wall seconds by cause. The causes
**tile the time**: in every :meth:`Account.snapshot` they sum to
``t - since``, the remainder stated as ``unaccounted_s`` and never folded
into a neighbour. Each cause is read where the program already writes it:

================  ==========================================================
``step_s``        a step record's ``step_time_s`` less its ``data_s`` and
                  ``straggle_s``; a generation's first step counts as one
                  median step of that generation (``_settle``)
``input_wait_s``  ``data_s``: the loop waited for its batch
``loop_s``        ``gap_s`` between two steps, less a save's stall or a
                  profile's write that fell into it
``straggle_s``    ``straggle_s``: a chaos spec's injected sleep
``save_stall_s``  ``ckpt_snapshot_done``: ``seconds`` + ``waited_s``
``profile_s``     ``profile_written``: ``seconds`` (a requested profile's
                  end and write)
``dead_worker_s`` a generation's last accounted moment to ``worker_crash``:
                  the step that was in flight and the reap (the program does
                  not know when it was killed)
``quiesce_s``     ``quiesce_sent`` (or the last step recorded after it) to
                  ``worker_exit``, less the drain's save stall
``decide_s``      ``worker_crash`` / ``worker_exit`` to the next ``spawn``
``boot_s``        ``spawn`` to ``trainer_built``
``restore_s``     ``trainer_built`` to ``restored``
``first_step_s``  ``restored`` to the generation's first step record, less
                  that one median step: tracing, lowering, the program's load
================  ==========================================================

Beside the tiling, a re-labelling of part of ``step_s``: when a generation's
``restored`` carries ``step = r``, every step recorded before it with a
number above ``r`` was thrown away — its share of ``step_s`` is added to
``wasted_s`` and it counts in ``steps_wasted``, once for each time it was
run and lost. ``step_s`` and ``wasted_s`` only ever grow, and ``step_s -
wasted_s`` over any interval is the time spent on steps that were kept.

Seconds are wall seconds of the agent's slots on the timeline's clock
(``time.time()``): multiply by ``chips`` for chip-seconds. What a snapshot
cannot know yet — the step in flight since the last record — is in
``unaccounted_s`` until the next record says where it went.

No clock, no jax: the account is a function of the lines it was fed
(easylint's purity rule holds it to that), so the chaos invariants and
``scripts/measure_recovery.py`` take their lost steps from
:func:`wasted_steps` too, and a test replays a recorded job through it.
"""

from __future__ import annotations

import json
import os
import statistics
from typing import Any, Dict, Iterable, List, Optional

Line = Dict[str, Any]

#: The causes, in the order a generation meets them. With ``unaccounted_s``
#: they are the keys of a snapshot's ``seconds``.
CAUSES = (
    "boot_s", "restore_s", "first_step_s", "step_s", "input_wait_s",
    "loop_s", "straggle_s", "save_stall_s", "profile_s", "quiesce_s",
    "dead_worker_s", "decide_s",
)

#: Timeline phases that move the account's bottom line: the agent emits a
#: ``goodput`` phase with the snapshot right after it has fed one.
SNAPSHOT_AFTER = frozenset(("ckpt_committed", "first_step_done", "restored"))

#: A generation's first step is priced once this many later steps of it are
#: known (or when the generation ends, if sooner): ``step_s`` may not fall,
#: so the median is taken once and kept.
_SETTLE_AFTER = 8


def wasted_steps(steps: Iterable[int], restored_step: int) -> List[int]:
    """The distinct step numbers among ``steps`` (run before a generation
    switch) that lie above the step the next generation restored: the work
    the switch threw away."""
    return sorted({s for s in map(int, steps) if s > restored_step})


class Account:
    """Cumulative seconds by cause since the agent's first ``spawn``; see
    the module's docstring. Lines are fed in the order of their ``t``."""

    def __init__(self, chips: int = 1):
        self.chips = int(chips)
        self.since: Optional[float] = None
        self.t = 0.0
        self.seconds: Dict[str, float] = dict.fromkeys(CAUSES, 0.0)
        self.wasted_s = 0.0
        self.steps_run = 0
        self.steps_wasted = 0
        self.last_kept_step = 0
        self._gen: Optional[int] = None
        # the moment up to which the running generation's time has a cause
        self._cursor = 0.0
        # stall seconds already put down (a save's, a profile's) that the
        # next leg to close — a gap, a drain, a death — contains
        self._stalled = 0.0
        self._quiesce_t: Optional[float] = None
        self._exit_t: Optional[float] = None
        # the running generation's first step: its number once recorded,
        # and the later steps' shares while it waits to be priced
        self._first_step: Optional[int] = None
        self._shares: Optional[List[float]] = None
        # steps that a later restore can still throw away: one by one above
        # the newest commit, one entry (step, count, seconds) a commit below
        self._live: Dict[int, float] = {}
        self._committed: List[tuple] = []

    # ------------------------------------------------------------------ feed
    def feed(self, line: Line) -> None:
        """One line of either file: a step record or a timeline event."""
        try:
            t = float(line["t"])
            if "phase" in line:
                phase, gen = str(line["phase"]), int(line["gen"])
            else:
                phase, gen = None, int(line["generation"])
                float(line["step_time_s"]), int(line["step"])
        except (KeyError, TypeError, ValueError):
            return  # not a line of either file
        if phase == "goodput":
            return  # the account's own
        if phase == "spawn":
            self._spawn(gen, t)
        if self.since is None:
            return
        self.t = max(self.t, t)
        if gen != self._gen or self._exit_t is not None:
            return  # a preflight's, a standby's, a zombie's
        if phase is None:
            self._step(line, t)
        elif phase == "trainer_built":
            self._close("boot_s", t)
        elif phase == "restored":
            self._close("restore_s", t)
            self._restored(int(line.get("step", 0)))
        elif phase == "first_step_done":
            # a few ms after the first record, which closed the leg already
            if self._first_step is None:
                self._close("first_step_s", t)
        elif phase == "ckpt_snapshot_done":
            self._stall("save_stall_s", float(line.get("seconds", 0.0))
                        + float(line.get("waited_s", 0.0)))
        elif phase == "profile_written":
            self._stall("profile_s", float(line.get("seconds", 0.0)))
        elif phase == "ckpt_committed":
            self._commit(int(line.get("step", 0)))
        elif phase == "quiesce_sent":
            self._quiesce_t = t
        elif phase == "worker_crash":
            self._close("dead_worker_s", t)
            self._worker_gone(t)
        elif phase == "worker_exit":
            if self._quiesce_t is not None:
                # what lies between the last record and the signal is the
                # loop's own, unreported: it stays unaccounted
                self._cursor = max(self._cursor, self._quiesce_t)
            self._close("quiesce_s", t)
            self._worker_gone(t)

    def _spawn(self, gen: int, t: float) -> None:
        if self.since is None:
            self.since = t
        elif self._exit_t is not None:
            self.seconds["decide_s"] += max(t - self._exit_t, 0.0)
        self._settle()
        self._gen, self._cursor, self._stalled = gen, t, 0.0
        self._quiesce_t = self._exit_t = self._first_step = None

    def _worker_gone(self, t: float) -> None:
        self._exit_t = t
        self._settle()

    def _close(self, cause: str, t: float) -> None:
        """``[cursor, t]`` goes to ``cause``, less the stalls inside it."""
        span = t - self._cursor
        if span <= 0:
            return  # a preflight built its trainer before its spawn
        self.seconds[cause] += span - min(self._stalled, span)
        self._cursor, self._stalled = t, 0.0

    def _stall(self, cause: str, seconds: float) -> None:
        self.seconds[cause] += seconds
        self._stalled += seconds

    def _step(self, rec: Line, t: float) -> None:
        step = int(rec["step"])
        self.steps_run += 1
        self.last_kept_step = step
        if self._first_step is None:
            # The generation's first step: tracing, lowering and the
            # program's load beside one step. The leg is first_step_s whole
            # until the step is priced.
            self._close("first_step_s", t)
            self._first_step, self._shares = step, []
            self._live[step] = 0.0
            return
        data = float(rec.get("data_s") or 0.0)
        straggle = float(rec.get("straggle_s") or 0.0)
        share = float(rec["step_time_s"]) - data - straggle
        self.seconds["step_s"] += share
        self.seconds["input_wait_s"] += data
        self.seconds["straggle_s"] += straggle
        gap = rec.get("gap_s")
        if gap is not None:
            self.seconds["loop_s"] += float(gap) - min(self._stalled,
                                                       float(gap))
        # a record from before PR 33 names no gap: a stall inside it is
        # known, the rest of it stays unaccounted
        self._cursor, self._stalled = max(self._cursor, t), 0.0
        self._live[step] = share
        if self._shares is not None:
            self._shares.append(share)
            if len(self._shares) >= _SETTLE_AFTER:
                self._settle()

    def _settle(self) -> None:
        """Price the generation's first step: one median step of the steps
        after it moves from ``first_step_s`` to ``step_s``."""
        shares, self._shares = self._shares, None
        if not shares:
            return  # priced already, or one step alone: nothing to price by
        share = statistics.median(shares)
        self.seconds["step_s"] += share
        self.seconds["first_step_s"] -= share
        self._live[self._first_step] = share

    def _commit(self, step: int) -> None:
        """A restore lands on a commit: the steps at or under this one are
        thrown away together or not at all, so they are kept as one sum."""
        if self._first_step is not None and self._first_step <= step:
            self._settle()  # priced before it is summed
        under = [s for s in self._live if s <= step]
        if under:
            self._committed.append(
                (step, len(under), sum(self._live.pop(s) for s in under)))

    def _restored(self, step: int) -> None:
        for s in wasted_steps(self._live, step):
            self.wasted_s += self._live.pop(s)
            self.steps_wasted += 1
        while self._committed and self._committed[-1][0] > step:
            _, count, seconds = self._committed.pop()
            self.wasted_s += seconds
            self.steps_wasted += count
        self.last_kept_step = step

    # -------------------------------------------------------------- snapshot
    def snapshot(self) -> Optional[Dict[str, Any]]:
        """``{t, since, chips, seconds, wasted_s, steps_run, steps_wasted,
        last_kept_step}``: ``t`` is the newest line's, ``seconds`` the
        causes and ``unaccounted_s``, which sum to ``t - since``. None
        before the first ``spawn``."""
        if self.since is None:
            return None
        seconds = dict(self.seconds)
        seconds["unaccounted_s"] = (self.t - self.since) - sum(
            seconds.values())
        return {"t": self.t, "since": self.since, "chips": self.chips,
                "seconds": seconds, "wasted_s": self.wasted_s,
                "steps_run": self.steps_run,
                "steps_wasted": self.steps_wasted,
                "last_kept_step": self.last_kept_step}


class Tail:
    """The lines appended to a JSONL file since the last call, by offset:
    none is missed or given twice, and a torn last line waits for its
    newline. Starts at the file's end as it is now — what an earlier agent
    of this name left is not this one's account."""

    def __init__(self, path: str):
        self.path = path
        try:
            self._offset = os.path.getsize(path)
        except OSError:
            self._offset = 0

    def read_new(self) -> List[Line]:
        try:
            with open(self.path, "rb") as f:
                f.seek(self._offset)
                data = f.read()
        except OSError:
            return []
        whole = data.rfind(b"\n") + 1
        self._offset += whole
        out = []
        for raw in data[:whole].splitlines():
            try:
                line = json.loads(raw)
            except ValueError:
                continue  # cut by a kill, finished by the next writer
            if isinstance(line, dict):
                out.append(line)
        return out

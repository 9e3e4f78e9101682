"""The job's own account of its chip-seconds (``elastic/goodput.py``): the
causes tile the time on every snapshot of a recorded killed-and-resumed job
and of made-up ones whose answers can be worked out by hand; ``step_s`` and
``wasted_s`` never fall; the files are read by offset. No jax, no process."""

import ast
import json
import os
import sys
import time

import pytest

from easydl_tpu.analysis.rules.metric_names import (KNOWN_LABELS,
                                                    REGISTERED_METRICS)
from easydl_tpu.analysis.rules.purity import PURE_PATHS
from easydl_tpu.elastic import goodput, timeline
from easydl_tpu.elastic.agent import Agent
from easydl_tpu.obs.registry import validate_metric_name
from easydl_tpu.proto import easydl_pb2 as pb

from test_timeline_phases import _StandIn, _run

HERE = os.path.dirname(os.path.abspath(__file__))


def replay(lines, chips=1):
    """Feed ``lines`` in the order of their ``t``; the snapshot after every
    line, so that a test can hold each of them to the tiling."""
    account = goodput.Account(chips)
    snaps = []
    for line in sorted(lines, key=lambda l: l.get("t", 0.0)):
        account.feed(line)
        snap = account.snapshot()
        if snap is not None:
            snaps.append(snap)
    return account, snaps


def holds(snaps):
    """The tiling to 1e-6 s on every snapshot, and the two counters."""
    assert snaps
    for snap in snaps:
        assert set(snap["seconds"]) == set(goodput.CAUSES) | {"unaccounted_s"}
        assert sum(snap["seconds"].values()) == pytest.approx(
            snap["t"] - snap["since"], abs=1e-6)
    for a, b in zip(snaps, snaps[1:]):
        assert b["seconds"]["step_s"] >= a["seconds"]["step_s"]
        assert b["wasted_s"] >= a["wasted_s"]
        assert b["steps_wasted"] >= a["steps_wasted"]


class Job:
    """Lines as an agent and its worker write them, on a clock that the
    test moves: a step of 1.0 s (0.05 of it waiting for input) after a gap
    of 0.1, a generation's start of 3 + 1 + 2.5 s."""

    def __init__(self, t=1000.0, fields=True):
        self.t, self.lines, self.fields = t, [], fields

    def event(self, phase, gen, after=0.0, **data):
        self.t += after
        self.lines.append({"t": self.t, "phase": phase, "gen": gen, **data})

    def record(self, gen, step, step_time_s=1.0, gap_s=0.1, **inside):
        self.t += gap_s + step_time_s
        rec = {"step": step, "generation": gen, "t": self.t, "loss": 1.0,
               "step_time_s": step_time_s}
        if self.fields:
            rec.update(gap_s=gap_s, data_s=0.05, **inside)
        self.lines.append(rec)

    def start(self, gen, restored, mode="cold"):
        """``spawn`` to ``first_step_done``: 3 + 1 + 2.5 s, the first
        record (no gap before it) 2 ms before ``first_step_done``."""
        self.event("spawn", gen, mode=mode)
        self.event("worker_main_start", gen, after=0.5)
        self.event("trainer_built", gen, after=2.5)
        self.event("restored", gen, after=1.0, step=restored)
        self.t += 2.498
        rec = {"step": restored + 1, "generation": gen, "t": self.t,
               "loss": 1.0, "step_time_s": 2.4}
        if self.fields:
            rec["data_s"] = 0.01
        self.lines.append(rec)
        # the next step's gap counts from the record, not from this
        self.lines.append({"t": self.t + 0.002, "phase": "first_step_done",
                           "gen": gen, "step": restored + 1})

    def steps(self, gen, first, last, **kw):
        for step in range(first, last + 1):
            self.record(gen, step, **kw)

    def save(self, gen, step, stall=0.5, waited=0.0):
        """The save after ``step``'s record: its stall is in the NEXT
        record's gap, which the caller makes ``0.1 + stall + waited``."""
        self.lines.append({"t": self.t + 0.05 + stall + waited,
                           "phase": "ckpt_snapshot_done", "gen": gen,
                           "step": step, "seconds": stall,
                           "waited_s": waited})

    def commit(self, gen, step):
        self.lines.append({"t": self.t - 0.3, "phase": "ckpt_committed",
                           "gen": gen, "step": step, "seconds": 2.0})

    def kill(self, gen, in_flight=0.4, reap=0.3, decide=0.2):
        """SIGKILL ``in_flight`` into the next step; the agent reaps the
        worker ``reap`` later and spawns ``decide`` after that."""
        self.event("worker_crash", gen, after=in_flight + reap, code=-9)
        self.t += decide


def one_generation(job, gen, restored, last, save_at=(), commits=()):
    job.start(gen, restored)
    for step in range(restored + 2, last + 1):
        stalled = step - 1 in save_at
        job.record(gen, step, gap_s=0.6 if stalled else 0.1)
        if step in save_at:
            job.save(gen, step)
        if step - 3 in commits:  # a commit lands three steps after its save
            job.commit(gen, step - 3)


def no_kill():
    job = Job()
    one_generation(job, 1, 0, 30, save_at=(10, 20), commits=(10, 20))
    return job


def kill_after_a_committed_save():
    """Saves at 10 and 20, both committed; killed at 23; restores 20."""
    job = no_kill()
    del job.lines[next(i for i, l in enumerate(job.lines)
                       if l.get("step") == 24 and "phase" not in l):]
    job.t = max(l["t"] for l in job.lines if "phase" not in l)
    job.kill(1)
    one_generation(job, 2, 20, 30)
    return job


def kill_before_the_newer_save_committed():
    """The benchmark cell's shape: the save at 20 has not committed when
    the worker is killed at 23, so the resume restores 10."""
    job = Job()
    one_generation(job, 1, 0, 23, save_at=(10, 20), commits=(10,))
    job.kill(1)
    one_generation(job, 2, 10, 30, save_at=(20,), commits=(20,))
    return job


def two_kills_that_throw_the_same_steps_away():
    job = Job()
    one_generation(job, 1, 0, 17, save_at=(10,), commits=(10,))
    job.kill(1)
    one_generation(job, 2, 10, 16)  # 11..16 again, no save before...
    job.kill(2)                     # ...the second kill
    one_generation(job, 3, 10, 25, save_at=(20,), commits=(20,))
    return job


def planned_quiesce():
    """A reshape: SIGUSR1 during step 15, the drain's save after it, a
    promoted preflight (built before its spawn) resumes at 15."""
    job = Job()
    one_generation(job, 1, 0, 14, save_at=(10,), commits=(10,))
    job.event("trainer_built", 2, after=0.05)  # the preflight's, early
    job.event("quiesce_sent", 1, after=0.3)
    job.t -= 0.35
    job.record(1, 15)
    job.event("quiesce_ckpt_begin", 1, after=0.01, step=15)
    job.event("ckpt_snapshot_done", 1, after=0.5, step=15, seconds=0.5,
              waited_s=0.0)
    job.event("ckpt_committed", 1, after=2.0, step=15, seconds=2.5)
    job.event("quiesce_exit", 1, after=0.01, step=15)
    job.event("worker_exit", 1, after=0.48, code=0)
    job.t += 0.2
    job.event("spawn", 2, mode="preflight")
    job.event("preflight_go", 2, after=0.1)
    job.event("restored", 2, after=0.9, step=15)
    job.t += 0.498
    job.lines.append({"step": 16, "generation": 2, "t": job.t, "loss": 1.0,
                      "step_time_s": 0.45, "data_s": 0.01})
    job.lines.append({"t": job.t + 0.002, "phase": "first_step_done",
                      "gen": 2, "step": 16})
    job.steps(2, 17, 30)
    return job


def records_from_before_pr_33():
    job = Job(fields=False)
    one_generation(job, 1, 0, 23, save_at=(10, 20), commits=(10,))
    job.kill(1)
    one_generation(job, 2, 10, 30)
    return job


SCENARIOS = {
    "no_kill": no_kill,
    "kill_after_a_committed_save": kill_after_a_committed_save,
    "kill_before_the_newer_save_committed":
        kill_before_the_newer_save_committed,
    "two_kills_that_throw_the_same_steps_away":
        two_kills_that_throw_the_same_steps_away,
    "planned_quiesce": planned_quiesce,
    "records_from_before_pr_33": records_from_before_pr_33,
}


# --------------------------------------------------------- a recorded job
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures",
                           "goodput_kill_resume.json")) as f:
        return json.load(f)


def test_recorded_job_tiles_on_every_snapshot(recorded):
    account, snaps = replay(recorded["timeline"] + recorded["records"])
    holds(snaps)
    last = snaps[-1]
    killed = [r["step"] for r in recorded["records"] if r["generation"] == 1]
    assert last["steps_wasted"] == max(killed) - 10 == 3
    assert last["steps_run"] == len(recorded["records"])
    assert last["last_kept_step"] == max(
        r["step"] for r in recorded["records"])
    assert abs(last["seconds"]["unaccounted_s"]) < 0.05
    # what the kill threw away is a part of the steps' time
    assert 0 < last["wasted_s"] < last["seconds"]["step_s"]


def test_replaying_the_files_gives_the_agents_own_snapshots(recorded):
    """The agent fed the lines a heartbeat at a time and emitted ``goodput``
    after each line that moves the bottom line; the same lines fed in one
    go give the same account at the same lines."""
    emitted = [e for e in recorded["timeline"] if e["phase"] == "goodput"]
    assert len(emitted) > 5
    account = goodput.Account()
    mine = []
    for line in sorted(recorded["timeline"] + recorded["records"],
                       key=lambda l: l["t"]):
        account.feed(line)
        if line.get("phase") in goodput.SNAPSHOT_AFTER:
            mine.append(account.snapshot())
    mine.append(account.snapshot())  # the agent's stop()
    assert [s["t"] for s in mine] == [e["t"] for e in emitted]
    for got, want in zip(mine, emitted):
        assert got["seconds"] == pytest.approx(want["seconds"], abs=1e-6)
        for key in ("wasted_s", "steps_run", "steps_wasted",
                    "last_kept_step", "since"):
            assert got[key] == pytest.approx(want[key], abs=1e-6)


def test_the_accounts_legs_are_the_timelines(recorded):
    _, snaps = replay(recorded["timeline"] + recorded["records"])
    seconds = snaps[-1]["seconds"]

    def span(start, end, gen):
        at = {e["phase"]: e["t"] for e in recorded["timeline"]
              if e["gen"] == gen and e["phase"] in (start, end)}
        return at[end] - at[start]

    assert seconds["boot_s"] == pytest.approx(
        span("spawn", "trainer_built", 1) + span("spawn", "trainer_built", 2))
    assert seconds["restore_s"] == pytest.approx(
        span("trainer_built", "restored", 1)
        + span("trainer_built", "restored", 2))
    crash = next(e["t"] for e in recorded["timeline"]
                 if e["phase"] == "worker_crash")
    spawn2 = next(e["t"] for e in recorded["timeline"]
                  if e["phase"] == "spawn" and e["gen"] == 2)
    assert seconds["decide_s"] == pytest.approx(spawn2 - crash)
    last_killed = max(r["t"] for r in recorded["records"]
                      if r["generation"] == 1)
    assert seconds["dead_worker_s"] == pytest.approx(crash - last_killed)
    assert seconds["save_stall_s"] == pytest.approx(sum(
        e["seconds"] + e["waited_s"] for e in recorded["timeline"]
        if e["phase"] == "ckpt_snapshot_done"))


# ------------------------------------------------------------ made-up jobs
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_every_snapshot_of_a_made_up_job_tiles(scenario):
    _, snaps = replay(SCENARIOS[scenario]().lines)
    holds(snaps)


def test_a_job_that_is_never_killed():
    account, snaps = replay(no_kill().lines)
    s = snaps[-1]["seconds"]
    assert (s["boot_s"], s["restore_s"]) == pytest.approx((3.0, 1.0))
    # 29 steps of 0.95 s after the first, and the first at their median
    assert s["step_s"] == pytest.approx(30 * 0.95)
    assert s["first_step_s"] == pytest.approx(2.498 - 0.95)
    assert s["input_wait_s"] == pytest.approx(29 * 0.05)
    assert s["save_stall_s"] == pytest.approx(2 * 0.5)
    assert s["loop_s"] == pytest.approx(29 * 0.1)  # the stalls are not in it
    assert s["unaccounted_s"] == pytest.approx(0.0, abs=1e-6)
    assert snaps[-1]["wasted_s"] == 0 and snaps[-1]["steps_wasted"] == 0
    assert snaps[-1]["steps_run"] == 30
    assert snaps[-1]["last_kept_step"] == 30


def test_one_kill_after_a_committed_save_wastes_the_steps_since_it():
    account, snaps = replay(kill_after_a_committed_save().lines)
    last = snaps[-1]
    assert last["steps_wasted"] == 3  # 21, 22, 23
    assert last["wasted_s"] == pytest.approx(3 * 0.95)
    assert last["seconds"]["dead_worker_s"] == pytest.approx(0.7)
    assert last["seconds"]["decide_s"] == pytest.approx(0.2)
    assert last["seconds"]["boot_s"] == pytest.approx(6.0)
    # 23 + 10 steps were run, 3 of them twice
    assert last["steps_run"] == 33
    assert last["seconds"]["step_s"] == pytest.approx(33 * 0.95)
    assert last["seconds"]["unaccounted_s"] == pytest.approx(0.0, abs=1e-6)
    # right after the restore the job stands at 20 again
    at_restore = next(s for s in snaps if s["steps_wasted"] == 3)
    assert at_restore["last_kept_step"] == 20


def test_an_uncommitted_newer_save_saves_nothing():
    account, snaps = replay(kill_before_the_newer_save_committed().lines)
    last = snaps[-1]
    assert last["steps_wasted"] == 13  # 11..23: N + 3
    assert last["wasted_s"] == pytest.approx(13 * 0.95)
    assert last["last_kept_step"] == 30
    # net progress: 30 steps kept of 23 + 20 run
    assert last["seconds"]["step_s"] - last["wasted_s"] == pytest.approx(
        30 * 0.95)


def test_two_kills_that_throw_the_same_steps_away_count_both_runs():
    account, snaps = replay(
        two_kills_that_throw_the_same_steps_away().lines)
    last = snaps[-1]
    assert last["steps_wasted"] == 7 + 6  # 11..17, then 11..16 again
    assert last["wasted_s"] == pytest.approx(13 * 0.95)
    assert last["seconds"]["dead_worker_s"] == pytest.approx(2 * 0.7)
    assert last["seconds"]["step_s"] - last["wasted_s"] == pytest.approx(
        25 * 0.95)
    holds(snaps)


def test_a_planned_quiesce_wastes_nothing():
    account, snaps = replay(planned_quiesce().lines)
    last = snaps[-1]
    s = last["seconds"]
    assert last["steps_wasted"] == 0 and last["wasted_s"] == 0
    # the record of step 15 to worker_exit, less the drain's save stall
    assert s["quiesce_s"] == pytest.approx(0.01 + 2.0 + 0.01 + 0.48)
    assert s["save_stall_s"] == pytest.approx(0.5 + 0.5)
    assert s["dead_worker_s"] == 0
    assert s["decide_s"] == pytest.approx(0.2)
    # the preflight built its trainer before its spawn: no second boot
    assert s["boot_s"] == pytest.approx(3.0)
    assert s["restore_s"] == pytest.approx(1.0 + 1.0)
    assert s["unaccounted_s"] == pytest.approx(0.0, abs=1e-6)
    assert last["last_kept_step"] == 30


def test_a_signal_that_finds_the_worker_between_steps_leaves_the_wait_out():
    job = Job()
    one_generation(job, 1, 0, 12)
    job.event("quiesce_sent", 1, after=0.04)
    job.event("worker_exit", 1, after=1.0, code=0)
    _, snaps = replay(job.lines)
    assert snaps[-1]["seconds"]["quiesce_s"] == pytest.approx(1.0)
    assert snaps[-1]["seconds"]["unaccounted_s"] == pytest.approx(0.04)


def test_records_from_before_pr_33_go_to_steps_and_unaccounted():
    account, snaps = replay(records_from_before_pr_33().lines)
    s = snaps[-1]["seconds"]
    assert s["input_wait_s"] == 0 and s["loop_s"] == 0
    assert s["step_s"] == pytest.approx((23 + 20) * 1.0)
    # the gaps nobody named, less the stalls the timeline did name
    assert s["unaccounted_s"] == pytest.approx(
        (22 + 19) * 0.1 + 2 * 0.5 - 2 * 0.5)
    assert snaps[-1]["steps_wasted"] == 13


def test_a_generations_first_step_counts_one_median_step():
    job = Job()
    job.start(1, 0)
    _, snaps = replay(job.lines)
    first = snaps[-1]["seconds"]
    assert first["first_step_s"] == pytest.approx(2.5, abs=3e-3)
    assert first["step_s"] == 0.0
    for i, step_time_s in enumerate((1.0, 1.2, 0.8, 1.0, 1.0, 1.0, 1.0)):
        job.record(1, 2 + i, step_time_s=step_time_s)
    _, snaps = replay(job.lines)
    assert snaps[-1]["seconds"]["first_step_s"] == pytest.approx(
        2.5, abs=3e-3)  # not priced yet: seven steps after it
    job.record(1, 9, step_time_s=3.0)
    _, snaps = replay(job.lines)
    s = snaps[-1]["seconds"]
    assert s["first_step_s"] == pytest.approx(2.498 - 0.95)
    assert s["step_s"] == pytest.approx(0.95 + 7.0 - 7 * 0.05 + 2.95)
    holds(snaps)
    # a generation killed before it is priced is priced by what it ran
    short = Job()
    one_generation(short, 1, 0, 3)
    short.kill(1)
    _, snaps = replay(short.lines)
    assert snaps[-1]["seconds"]["step_s"] == pytest.approx(3 * 0.95)


def test_a_profiles_write_is_not_the_loops_time():
    job = Job()
    one_generation(job, 1, 0, 5)
    job.lines.append({"t": job.t + 4.2, "phase": "profile_written", "gen": 1,
                      "seconds": 4.11, "path": "x", "bytes": 1})
    job.record(1, 6, gap_s=4.3)
    _, snaps = replay(job.lines)
    s = snaps[-1]["seconds"]
    assert s["profile_s"] == pytest.approx(4.11)
    assert s["loop_s"] == pytest.approx(4 * 0.1 + 0.19)
    holds(snaps)


def test_lines_of_other_processes_move_nothing():
    job = Job()
    job.event("standby_warm_ready", -1)  # before the first spawn: no account
    account, snaps = replay(job.lines)
    assert snaps == [] and account.snapshot() is None
    one_generation(job, 1, 0, 12)
    want = replay(job.lines)[1][-1]
    job.event("worker_main_start", 2)    # a preflight of the next one
    job.event("trainer_built", 2)
    job.lines.append({"step": 3, "generation": 0, "t": job.t, "loss": 1.0,
                      "step_time_s": 9.0})  # a zombie of an older one
    job.lines.append({"t": job.t, "phase": "goodput", "gen": 1,
                      "seconds": {"step_s": 1e9}})  # the agent's own
    job.lines.append({"neither": "kind"})
    job.lines.append({"t": job.t, "phase": "spawn"})  # no generation
    got = replay(job.lines)[1][-1]
    assert got == want


@pytest.mark.parametrize("steps,restored,wasted", [
    (range(1, 54), 25, list(range(26, 54))),   # the benchmark's cell: 28
    ([11, 12, 13, 11, 12], 10, [11, 12, 13]),  # two agents' records of one job
    (range(1, 11), 10, []),                    # a drain: nothing lost
    ([], 0, []),
])
def test_wasted_steps(steps, restored, wasted):
    assert goodput.wasted_steps(steps, restored) == wasted


# ------------------------------------------------------- reading the files
def test_tail_reads_by_offset_and_waits_for_a_torn_lines_newline(tmp_path):
    path = str(tmp_path / "metrics-a0.jsonl")
    with open(path, "w") as f:
        f.write('{"step": 1, "old": true}\n')  # an earlier agent's
    tail = goodput.Tail(path)
    assert tail.read_new() == []
    with open(path, "a") as f:
        f.write('{"step": 2}\n{"step": 3}\n{"step": 4, "lo')
    assert tail.read_new() == [{"step": 2}, {"step": 3}]
    assert tail.read_new() == []
    with open(path, "a") as f:
        f.write('ss": 1.5}\nnot json\n[1, 2]\n{"step": 5}\n')
    assert tail.read_new() == [{"step": 4, "loss": 1.5}, {"step": 5}]
    assert goodput.Tail(str(tmp_path / "missing")).read_new() == []


def test_a_torn_last_line_is_fed_whole_or_not_at_all(tmp_path):
    lines = kill_before_the_newer_save_committed().lines
    paths = [str(tmp_path / "timeline-a0.jsonl"),
             str(tmp_path / "metrics-a0.jsonl")]
    tails = [goodput.Tail(p) for p in paths]
    account, snaps = goodput.Account(), []

    def feed_new():
        for new in sorted((l for t in tails for l in t.read_new()),
                          key=lambda l: l["t"]):
            account.feed(new)
            snaps.append(account.snapshot())

    for line in sorted(lines, key=lambda l: l["t"]):
        path = paths[0 if "phase" in line else 1]
        text = json.dumps(line) + "\n"
        with open(path, "a") as f:
            f.write(text[:-7])
        feed_new()  # finds the line torn: it waits
        with open(path, "a") as f:
            f.write(text[-7:])
    feed_new()
    holds(snaps)
    assert snaps[-1] == replay(lines)[1][-1]
    assert snaps[-1]["steps_run"] == 43 and snaps[-1]["steps_wasted"] == 13


# ------------------------------------------------------------- the module
def test_the_module_is_pure_and_its_series_are_declared():
    path = "easydl_tpu/elastic/goodput.py"
    assert path in PURE_PATHS
    with open(os.path.join(os.path.dirname(HERE), path)) as f:
        tree = ast.parse(f.read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported <= {"__future__", "json", "os", "statistics", "typing"}
    for name in ("easydl_job_chip_seconds_total", "easydl_job_goodput_ratio"):
        assert validate_metric_name(name) in REGISTERED_METRICS
    assert {"agent", "reason"} <= KNOWN_LABELS


# -------------------------------------------------------------- the agent
@pytest.fixture
def agent(tmp_path):
    a = Agent("a0", "localhost:1", str(tmp_path), slots=4, platform="cpu",
              worker_argv=[sys.executable, "-c",
                           "import time; time.sleep(60)"])
    timeline.add_listener(a._on_timeline_emit)
    yield a
    timeline.remove_listener(a._on_timeline_emit)
    a._terminate_worker(graceful=False)


def _worker_writes(a, phase, **data):
    """A line of the worker's, another process: no listener hears it."""
    with open(a.timeline_path, "a") as f:
        f.write(json.dumps({"t": time.time(), "phase": phase,
                            "gen": a._applied_key[0], **data}) + "\n")


def test_the_phase_stands_between_a_legs_boundaries_and_the_leg_reads_on(
        agent):
    a = agent
    a._apply(_run(1))
    real, a._proc = a._proc, _StandIn()
    real.kill()
    real.wait()
    a._feed_goodput()
    assert a.goodput()["seconds"]["boot_s"] == 0.0
    a._apply(pb.Directive(kind=pb.DirectiveKind.QUIESCE))
    time.sleep(0.05)
    _worker_writes(a, "ckpt_committed", step=5, seconds=0.1)
    a._feed_goodput()  # emits goodput between quiesce_sent and worker_exit
    a._proc.code = 0
    a._refresh_state()
    time.sleep(0.05)
    _worker_writes(a, "restored", step=5)  # a stray line of the old worker
    a._feed_goodput()  # ... and between worker_exit and spawn
    a._apply(_run(2))
    a._feed_goodput(last=True)
    phases = [e["phase"] for e in timeline.read(a.timeline_path)]
    assert phases == ["spawn", "quiesce_sent", "ckpt_committed", "goodput",
                      "worker_exit", "restored", "goodput", "spawn",
                      "goodput"]
    for phase in ("worker_exit", "spawn"):
        assert a._m_phase_seconds.value(agent="a0", phase=phase) >= 0.05
    assert a._m_phase_seconds.value(agent="a0", phase="goodput") == 0.0
    # the account heard the agent's own lines through the file as well
    snap = a.goodput()
    assert snap["chips"] == 4
    assert snap["seconds"]["quiesce_s"] >= 0.05
    assert snap["seconds"]["decide_s"] >= 0.05
    emitted = [e for e in timeline.read(a.timeline_path)
               if e["phase"] == "goodput"]
    assert emitted[-1]["seconds"] == snap["seconds"]
    assert emitted[-1]["feeds"] == 3 and emitted[-1]["feed_s"] > 0
    # chip-seconds: the slots' four chips
    quiesce = a._m_chip_seconds.value(agent="a0", reason="quiesce")
    assert quiesce == pytest.approx(4 * snap["seconds"]["quiesce_s"])

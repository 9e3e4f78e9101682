"""A training job through master -> agent -> worker, with events anchored
to step numbers: periodic saves, one SIGKILL of the worker, the resume.

This process hosts the master and the agent and never initialises a jax
backend while a worker lives: the worker holds the chip. The schedule, all
from the traffic file (``save_every`` = N, ``kill_after_save_steps``):

- set-up: start master and agent; the worker trains to periodic save C0
  (step N); the window opens when C0's ``COMMITTED`` marker appears;
- window: periodic save S1 starts at step 2N with no other save in flight;
  the worker is SIGKILLed (``Agent.kill_worker_hard``) as soon as the record
  of step 2N + ``kill_after_save_steps`` appears; the next generation
  restores the newest committed checkpoint and trains until the window ends.

Every run loses the same work and restores the same step. After the window
the agent and master are stopped and every worker has ended; only then does
this process look at the chip itself, to name the device as jax reports it
and to read the memory the worker's step program needs from the compiler.

A mix may ask for more, each by a key of its own file; a mix without the key
runs what is written above, call for call:

- ``mesh`` and ``resume_mesh``: the job's meshes are the master's mesh-shape
  policy's (``mesh_policy`` in the job's configuration, pinned to ``mesh``),
  and in front of the SIGKILL the pin is moved to ``resume_mesh`` on the
  master this process hosts (the live generation was told its mesh when it
  formed): every generation formed after the kill is told that shape and
  restores a checkpoint saved under the other. ``correct`` then also holds
  every step record to the mesh of its side of the kill and a kept
  checkpoint, restored under both meshes once the chips are free, to the
  bits its files hold, and the memory is the larger of the two step
  programs';
- ``resume_in_setup``: the kill and the whole resume are set-up. The SIGKILL
  falls at the record of step N + ``kill_after_save_steps``, a count the mix
  sets above the steps C0's commit lasts, so that every run loses the same
  steps and restores step N; the window opens when the first step record of
  the resumed generation appears and holds that one generation (a resume
  that does not fit a window beside sixteen steps: four chips take 48-53 s
  from the SIGKILL to that record). A program the resumed generation
  compiles is compiled in set-up;
- ``replay_loss_steps`` K: the first K resumed steps (default 1) are held to
  the losses the first run had at them.
"""

from __future__ import annotations

import math
import os
import re
import shutil
import time
from typing import Any, Dict, List, Optional


def _committed(ckpt_dir: str) -> Dict[str, float]:
    """``{step: mtime of its COMMITTED marker}`` under ``ckpt_dir``."""
    out = {}
    try:
        names = os.listdir(ckpt_dir)
    except OSError:
        return out
    for name in names:
        if name.startswith("step_"):
            try:
                out[str(int(name[len("step_"):]))] = os.stat(
                    os.path.join(ckpt_dir, name, "COMMITTED")).st_mtime
            except (OSError, ValueError):
                continue
    return out


class ScheduleFailed(SystemExit):
    pass


def job_config(config: Dict[str, Any], mix: Dict[str, Any], corpus: str,
               seed: int) -> Dict[str, Any]:
    """The job's configuration as the master hands it to every worker
    (``job.json``). A mix that names its meshes opts the job into the
    master's mesh-shape policy, pinned to the first."""
    job = {
        "model": config["factory"], "model_kwargs": config["kwargs"],
        "global_batch": mix["global_batch"], "grad_accum": mix["grad_accum"],
        "data_dir": corpus, "total_steps": 10_000_000,
        "ckpt_interval": mix["save_every"], "seed": seed, "lr": mix["lr"],
        "mesh": {axis: int(size) for axis, size in (
            part.split("=") for part in config["mesh"].split(",") if part)
            if axis != "dp"},
    }
    if "mesh" in mix:
        job["mesh_policy"] = {"pin": mix["mesh"]}
    return job


def kill_step_of(mix: Dict[str, Any]) -> int:
    """The step whose record brings the SIGKILL: behind S1 at 2N, or behind
    C0 at N where the mix puts the resume into set-up."""
    saves = 1 if mix.get("resume_in_setup") else 2
    return saves * mix["save_every"] + mix["kill_after_save_steps"]


def run(run: Any) -> Dict[str, Any]:
    from lib import (devices as dev, goodput_events, timeline_reduce as tl,
                     traffic, worker_program)

    config, mix = run.config, run.traffic
    kwargs = config["kwargs"]
    n_save = mix["save_every"]
    meshes = [mix[key] for key in ("mesh", "resume_mesh") if key in mix]
    kill_in_setup = bool(mix.get("resume_in_setup"))
    if len(meshes) == 1:
        raise SystemExit("benchmark: a mix names `mesh` and `resume_mesh` "
                         "together or neither")
    job = os.path.join(run.workdir, "job")
    os.makedirs(job)
    corpus = traffic.write_corpus(
        os.path.join(run.workdir, "corpus"), run.seed, kwargs["vocab"],
        mix["tokens"]["corpus_tokens"], mix["tokens"]["support"])
    for key, value in mix.get("env", {}).items():
        os.environ[key] = value.format(workdir=run.workdir)
    os.environ["PYTHONPATH"] = run.root + os.pathsep + os.environ.get(
        "PYTHONPATH", "")

    from easydl_tpu.elastic.agent import Agent
    from easydl_tpu.elastic.master import Master

    worker_config = job_config(config, mix, corpus, run.seed)
    master = Master(job_name="benchmark", workdir=job, desired_workers=1,
                    min_workers=1, worker_config=worker_config).start()
    agent = Agent("a0", master.address, job, slots=run.cell["chips"],
                  platform=config["platform"]).start()
    metrics_path = os.path.join(job, "metrics-a0.jsonl")
    timeline_path = os.path.join(job, "timeline-a0.jsonl")
    ckpt_dir = os.path.join(job, "ckpt")
    deadline = time.monotonic() + mix["setup_timeout_s"]

    def worker_log() -> str:
        try:
            with open(os.path.join(job, "worker-a0.log"),
                      errors="replace") as f:
                return f.read()
        except OSError:
            return ""

    def last_record():
        recs = tl.read_jsonl(metrics_path)
        return recs[-1] if recs else None

    t_kill = killed_generation = None
    kill_step = kill_step_of(mix)
    commits: Dict[str, float] = {}  # as seen: the manager keeps only three
    device_seen = False

    def past_setup() -> None:
        if time.monotonic() > deadline:
            raise ScheduleFailed(
                f"benchmark: set-up did not end in {mix['setup_timeout_s']}s "
                f"(commits {sorted(_committed(ckpt_dir))}, killed at "
                f"{t_kill}); worker log ends: " + worker_log()[-2000:])

    def kill_when_due() -> None:
        """The SIGKILL, once, when the record of ``kill_step`` is there."""
        nonlocal t_kill, killed_generation
        commits.update(_committed(ckpt_dir))
        if t_kill is not None:
            return
        rec = last_record()
        if rec and rec["step"] >= kill_step:
            killed_generation = rec["generation"]
            if meshes:
                # the operator's pin, moved in front of the kill: the live
                # generation was told its mesh when it formed, the next
                # formation asks the policy
                master._mesh_policy.pinned = meshes[1]
            t_kill = time.time()
            agent.kill_worker_hard()

    try:
        while str(n_save) not in _committed(ckpt_dir):
            if not device_seen:
                # the worker's log names the device it found: no result
                # from a job that trains on anything else
                found = re.search(r"device: (\w+) \(", worker_log())
                if found and found.group(1) != config["platform"]:
                    raise dev.NoDevice(
                        f"this cell measures on a {config['platform']}; the "
                        f"worker found {found.group(1)!r}")
                device_seen = bool(found)
            past_setup()
            time.sleep(0.02)
        while kill_in_setup:  # the kill and the resume before the window
            kill_when_due()
            if t_kill is not None and \
                    last_record()["generation"] > killed_generation:
                break
            past_setup()
            time.sleep(0.02)
        t_open = time.time()
        setup_s = t_open - run.t_start
        t_close = t_open + run.seconds
        while time.time() < t_close:
            kill_when_due()
            time.sleep(0.02)
    finally:
        agent.stop()
        master.stop()
        gone = time.monotonic() + 60.0
        while agent.worker_pid is not None and time.monotonic() < gone:
            time.sleep(0.1)
    if agent.worker_pid is not None:
        raise ScheduleFailed("benchmark: a worker outlived the agent's stop")
    # seconds since the window closed at the end of each part behind it
    after = {"workers_gone": time.time() - t_close}

    records = tl.read_jsonl(metrics_path)
    timeline = tl.read_jsonl(timeline_path)
    with open(os.path.join(run.workdir, "worker.log"), "w") as f:
        f.write(worker_log())

    # ------------------------------------------------------ what happened
    window = tl.in_window(records, t_open, t_close)
    failures = []
    compared: Dict[str, List[float]] = {}  # name: [number, its limit]
    resumed = (tl.first_record_after(records, killed_generation)
               if t_kill is not None else None)
    restored = None
    if t_kill is None:
        failures.append(f"step {kill_step} was not reached in the window")
    elif resumed is None or resumed["t"] > t_close:
        failures.append("no step of a later generation inside the window")
    else:
        restored_events = [e for e in timeline if e["phase"] == "restored"
                           and e["gen"] == resumed["generation"]]
        restored = restored_events[0]["step"] if restored_events else None
        if str(restored) not in commits:
            failures.append(f"restored step {restored} is not a committed "
                            f"checkpoint ({sorted(commits)})")
        elif resumed["step"] != restored + 1:
            failures.append(f"first step after the resume is "
                            f"{resumed['step']}, restored {restored}")
        else:
            failures += _replayed_losses(
                records, killed_generation, restored,
                mix.get("replay_loss_steps", 1), mix["replay_loss_rtol"],
                compared)
    extra = tl.extra_generations(timeline)
    if extra:
        failures.append(f"{extra} generation(s) beyond the expected two")
    if meshes and t_kill is not None:
        astray = [r for r in records if r.get("mesh") != (
            meshes[0] if r["generation"] <= killed_generation else meshes[1])]
        compared["records_on_another_mesh"] = [len(astray), 0]
        if astray:
            failures.append(
                f"{len(astray)} step record(s) not under {meshes[0]} before "
                f"the kill and {meshes[1]} after it, the first: {astray[0]}")
    non_finite = sum(1 for r in window if not math.isfinite(r["loss"]))

    # ------------------------------------- the device, now that it is free
    if not meshes:
        shutil.rmtree(ckpt_dir, ignore_errors=True)  # gigabytes; read already
    device, memory = worker_program.device_and_step_memory(
        config, run.cell["chips"], worker_config, meshes[0] if meshes else None)
    memories = [memory] + [worker_program.device_and_step_memory(
        config, run.cell["chips"], worker_config, mesh)[1]
        for mesh in meshes[1:]]
    after["step_programs"] = time.time() - t_close
    restored_leaves = reread = None
    if meshes:
        # C0 where the job still keeps it (it keeps three), else its newest
        kept = sorted(int(step) for step in _committed(ckpt_dir))
        reread = n_save if n_save in kept else kept[-1]
        differ, restored_leaves = worker_program.restores_that_differ(
            config, run.cell["chips"], worker_config, ckpt_dir, reread, meshes)
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        after["restores_compared"] = time.time() - t_close
        compared["restored_leaves_that_differ"] = [len(differ), 0]
        if differ:
            failures.append(
                f"checkpoint {reread} restores to other bits than its files "
                f"hold, under {meshes[0]} or {meshes[1]}, in {len(differ)} "
                f"leaves ({restored_leaves} in the files): {differ[:5]}")
    # of a job that changes its mesh, the larger of its two step programs
    memory = max(memories, key=_program_bytes)
    step_busy_s = sum(r["step_time_s"] for r in window)
    artifacts = {
        "device": device,
        "chips": run.cell["chips"],
        "memory_peak_bytes": _program_bytes(memory),
        "step_memory": memory,
        "setup_s": setup_s,
        "window_s": t_close - t_open,
        "tokens_per_step": mix["global_batch"] * kwargs["seq_len"],
        "records": records, "timeline": timeline,
        "t_open": t_open, "t_close": t_close, "t_kill": t_kill,
        "killed_generation": killed_generation,
        "save_steps": [n_save, 2 * n_save], "commits": commits,
        "restored_step": restored,
        "attempted": len(window) + 2 + 2 * bool(meshes),
        "failed": non_finite + len(failures),
        "failures": failures,
        "after_window_s": after,
        "correct": not failures and non_finite == 0,
        "compared": compared,
        # No profiler runs in the worker and this process holds no chip:
        # how long the device ran steps is known only from the worker's own
        # clock around each blocking step.
        "busy": {"busy_s": step_busy_s, "window_s": t_close - t_open,
                 "busy_source": "worker step_time_s, host clock"},
        "breakdown": _breakdown(tl, records, timeline, t_kill,
                                killed_generation, 2 * n_save, step_busy_s),
    }
    if meshes:
        artifacts.update(meshes=meshes, step_memories=memories,
                         reread_step=reread, restored_leaves=restored_leaves)
    if kill_in_setup:
        # one generation fills the window: its rate is all its steps over
        # all its time, and the steady cells' step readers have steps to read
        artifacts.update(one_generation_window=True,
                         step_s=[r["step_time_s"] for r in window])
    # how far the account's two snapshots stand from the window's edges
    artifacts["goodput_edges"] = goodput_events.edge_distances(artifacts)
    return artifacts


def _program_bytes(memory: Dict[str, int]) -> int:
    return (memory["argument_bytes"] + memory["temp_bytes"]
            + memory["output_bytes"] - memory["alias_bytes"])


def _replayed_losses(records, killed_generation: int, restored: int,
                     steps: int, tol: float,
                     compared: Dict[str, List[float]]) -> List[str]:
    """The first ``steps`` steps behind the restored one, as the resumed job
    ran them against the first run's: what does not repeat its loss within
    ``tol``, and each relative gap into ``compared`` beside it."""
    failures = []
    for nth in range(1, steps + 1):
        runs = [r for r in records if r["step"] == restored + nth]
        before = [r for r in runs if r["generation"] <= killed_generation]
        after = [r for r in runs if r["generation"] > killed_generation]
        gap: Optional[float] = None
        if before and after:
            gap = abs(before[0]["loss"] - after[0]["loss"]) / abs(
                before[0]["loss"])
            compared[f"replay_loss_rel_{nth}"] = [gap, tol]
        if gap is None or not gap <= tol:
            failures.append(
                f"step {restored + nth} replayed with loss "
                f"{after[0]['loss'] if after else None}, first run had "
                f"{before[0]['loss'] if before else None} (rtol {tol})")
    return failures


def _breakdown(tl, records, timeline, t_kill, killed_generation, save_step,
               step_busy_s):
    """Where the window went, by the worker's clock and the timeline: the
    steps, and the stretches in which the chip stood idle."""
    gaps = []
    gen = tl.resuming_generation(records, killed_generation) \
        if t_kill is not None else None
    spawn = tl.phase_t(timeline, "spawn", gen) if gen is not None else None
    if spawn is not None:
        gaps.append(["resume: kill to spawn (agent, master, reap)",
                     spawn - t_kill])
        for name, start, end in (
                ("resume: spawn to trainer_built (process, TPU runtime, "
                 "model)", "spawn", "trainer_built"),
                ("resume: trainer_built to restored (checkpoint read)",
                 "trainer_built", "restored"),
                ("resume: restored to first_step_done (program load, one "
                 "step)", "restored", "first_step_done")):
            span = tl.phase_span_s(timeline, gen, start, end)
            if span is not None:
                gaps.append([name, span])
    stall = tl.save_stall_s(records, save_step)
    if stall is not None:
        gaps.append(["save: step loop stalled at the periodic save", stall])
    return {"device_ops": [["train steps (worker step_time_s, host clock)",
                            step_busy_s]],
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]}

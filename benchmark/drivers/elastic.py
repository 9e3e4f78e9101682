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
"""

from __future__ import annotations

import math
import os
import re
import shutil
import time
from typing import Any, Dict


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


def run(run: Any) -> Dict[str, Any]:
    from lib import (devices as dev, goodput_events, timeline_reduce as tl,
                     traffic)

    config, mix = run.config, run.traffic
    kwargs = config["kwargs"]
    n_save, kill_after = mix["save_every"], mix["kill_after_save_steps"]
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

    worker_config = {
        "model": config["factory"], "model_kwargs": kwargs,
        "global_batch": mix["global_batch"], "grad_accum": mix["grad_accum"],
        "data_dir": corpus, "total_steps": 10_000_000,
        "ckpt_interval": n_save, "seed": run.seed, "lr": mix["lr"],
        "mesh": {axis: int(size) for axis, size in (
            part.split("=") for part in config["mesh"].split(",") if part)
            if axis != "dp"},
    }
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
    commits: Dict[str, float] = {}  # as seen: the manager keeps only three
    device_seen = False
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
            if time.monotonic() > deadline:
                raise ScheduleFailed(
                    f"benchmark: no committed checkpoint at step {n_save} "
                    f"after {mix['setup_timeout_s']}s; worker log ends: "
                    + worker_log()[-2000:])
            time.sleep(0.02)
        t_open = time.time()
        setup_s = t_open - run.t_start
        t_close = t_open + run.seconds
        kill_step = 2 * n_save + kill_after
        while time.time() < t_close:
            commits.update(_committed(ckpt_dir))
            if t_kill is None:
                rec = last_record()
                if rec and rec["step"] >= kill_step:
                    killed_generation = rec["generation"]
                    t_kill = time.time()
                    agent.kill_worker_hard()
            time.sleep(0.02)
    finally:
        agent.stop()
        master.stop()
        gone = time.monotonic() + 60.0
        while agent.worker_pid is not None and time.monotonic() < gone:
            time.sleep(0.1)
    if agent.worker_pid is not None:
        raise ScheduleFailed("benchmark: a worker outlived the agent's stop")

    records = tl.read_jsonl(metrics_path)
    timeline = tl.read_jsonl(timeline_path)
    with open(os.path.join(run.workdir, "worker.log"), "w") as f:
        f.write(worker_log())
    shutil.rmtree(ckpt_dir, ignore_errors=True)  # gigabytes; read already

    # ------------------------------------------------------ what happened
    window = tl.in_window(records, t_open, t_close)
    failures = []
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
            before = [r for r in records if r["step"] == resumed["step"]
                      and r["generation"] <= killed_generation]
            tol = mix["replay_loss_rtol"]
            if not before or abs(before[0]["loss"] - resumed["loss"]) > \
                    tol * abs(before[0]["loss"]):
                failures.append(
                    f"step {resumed['step']} replayed with loss "
                    f"{resumed['loss']}, first run had "
                    f"{before[0]['loss'] if before else None} (rtol {tol})")
    extra = tl.extra_generations(timeline)
    if extra:
        failures.append(f"{extra} generation(s) beyond the expected two")
    non_finite = sum(1 for r in window if not math.isfinite(r["loss"]))

    # ------------------------------------- the device, now that it is free
    device, memory = _device_and_step_memory(run, worker_config)
    step_busy_s = sum(r["step_time_s"] for r in window)
    artifacts = {
        "device": device,
        "chips": run.cell["chips"],
        "memory_peak_bytes": memory["argument_bytes"] + memory["temp_bytes"]
        + memory["output_bytes"] - memory["alias_bytes"],
        "step_memory": memory,
        "setup_s": setup_s,
        "window_s": t_close - t_open,
        "tokens_per_step": mix["global_batch"] * kwargs["seq_len"],
        "records": records, "timeline": timeline,
        "t_open": t_open, "t_close": t_close, "t_kill": t_kill,
        "killed_generation": killed_generation,
        "save_steps": [n_save, 2 * n_save], "commits": commits,
        "restored_step": restored,
        "attempted": len(window) + 2,
        "failed": non_finite + len(failures),
        "failures": failures,
        "correct": not failures and non_finite == 0,
        # No profiler runs in the worker and this process holds no chip:
        # how long the device ran steps is known only from the worker's own
        # clock around each blocking step.
        "busy": {"busy_s": step_busy_s, "window_s": t_close - t_open,
                 "busy_source": "worker step_time_s, host clock"},
        "breakdown": _breakdown(tl, records, timeline, t_kill,
                                killed_generation, 2 * n_save, step_busy_s),
    }
    # how far the account's two snapshots stand from the window's edges
    artifacts["goodput_edges"] = goodput_events.edge_distances(artifacts)
    return artifacts


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


def _device_and_step_memory(run: Any, worker_config: Dict[str, Any]):
    """With every worker gone: the device as jax reports it, and the bytes
    per device the worker's step program needs (the same ``Trainer`` the
    worker builds, compiled ahead — from the cache its run filled)."""
    import jax
    import jax.numpy as jnp
    import optax

    from easydl_tpu.utils.env import configure_compile_cache

    from lib import devices as dev, hlo, program

    configure_compile_cache()
    devices = dev.require(run.config["platform"], run.cell["chips"])
    _, trainer = program.build_trainer(
        run.config, worker_config["global_batch"],
        worker_config["grad_accum"], optax.adam(worker_config["lr"]),
        worker_config["seed"], devices)
    tokens = jax.ShapeDtypeStruct(
        (worker_config["global_batch"],
         worker_config["model_kwargs"]["seq_len"]), jnp.int32)
    compiled = trainer.step_fn.lower(
        trainer.abstract_state(),
        {"inputs": tokens, "targets": tokens}).compile()
    return dev.describe(devices), hlo.step_memory(compiled)

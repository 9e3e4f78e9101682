#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py          # one chip: device, kernel, train, resume,
                                  # elastic — GPT-2 345M at full width
    python chip_smoke.py --mesh   # four chips: only the mesh phase

Drives the training path once through the entry points a user would call —
``python -m easydl_tpu.models.run`` and master -> agent -> worker — on GPT-2
345M (24 layers, d_model 1024, 16 heads of 64, vocab 50304, seq 1024, bf16,
remat "dots") with the Pallas flash kernel, random weights and data from a
seed, and checks what comes out: kernel against the float32 reference,
falling finite losses, a committed checkpoint, a resume that continues from
it and finds the compile cache, and a SIGKILLed elastic worker recovered.

The parent is plain stdlib and never imports jax: a chip belongs to one
process at a time. Each phase is a child process (``--phase NAME``), strictly
one alive at a time, sharing one persistent compile cache (utils/env.py).
Every phase prints one JSON object on its own line; a phase that fails makes
the script exit non-zero with no result line. With no accelerator there is
no smaller run: the device phase fails. The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
#: Checkpoints, job workdirs and logs of a run; git-ignored, wiped per run.
WORK = os.path.join(HERE, ".chip_smoke")
#: The whole run must end inside the driver's 1200 s.
BUDGET_S = 1140.0
SEED = 0


@dataclasses.dataclass(frozen=True)
class Size:
    """What a phase runs at. The default is the real thing; only a test
    builds another one (tests/test_chip_smoke.py, on the CPU mesh)."""

    platform: str = "tpu"
    #: ``--model-arg``s of the zoo runner / ``model_kwargs`` of job.json
    model: tuple = (("size", "345m"), ("seq_len", 1024),
                    ("dtype", "bfloat16"), ("remat", True),
                    ("remat_policy", "dots"))
    vocab: int = 50304
    seq_len: int = 1024
    batch: int = 8
    #: flash kernel check: [batch, seq, heads, head_dim], bf16, causal
    attn_shape: tuple = (8, 1024, 16, 64)
    interpret: bool = False
    #: four-chip phase: global batch, and the one-device comparison's
    #: accumulation (32 rows of activations do not fit one chip; 4 at a
    #: time leave it 11.3 of 16 GiB — scripts/rehearse_tpu_compile.py)
    mesh_batch: int = 32
    mesh_accum: int = 8
    mesh_steps: int = 3

    @property
    def attention_line(self) -> str:
        """What the runner's log must say about attention."""
        if self.platform == "tpu":
            return "flash attention: compiled Pallas kernel on tpu"
        return "attention: XLA reference path"


REAL = Size()

# Stated tolerances. Kernel: bf16 inputs and outputs against the reference
# computed in float32 at highest matmul precision — bf16 keeps 8 bits, and
# 1024-long sums of such terms land within a few percent of the largest
# entry. Mesh: the same bf16 step reduced in a different order over devices.
KERNEL_FWD_ATOL = 2e-2
KERNEL_GRAD_RTOL = 3e-2   # of the reference gradient's largest |entry|
MESH_LOSS_RTOL = 5e-3


class PhaseFailed(Exception):
    pass


def need(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def finite(values) -> bool:
    return all(math.isfinite(x) for x in values)


# --------------------------------------------------------------- the phases
def phase_device(size: Size = REAL) -> dict:
    """Which device jax finds. Anything but the expected platform fails."""
    import jax

    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    need(dev.platform == size.platform,
         f"jax found no {size.platform}: {info}")
    return info


def phase_kernel(size: Size = REAL) -> dict:
    """Flash forward and gradients, compiled, against the f32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from easydl_tpu.ops.attention import _reference_attention
    from easydl_tpu.ops.flash_attention import flash_attention
    from easydl_tpu.utils.env import configure_compile_cache

    configure_compile_cache()
    shape = size.attn_shape
    scale = shape[-1] ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, scale=scale,
                              interpret=size.interpret)
        return (out.astype(jnp.float32) * w).sum(), out

    def ref_loss(q, k, v):
        out = _reference_attention(q, k, v, causal=True, scale=scale)
        return (out * w).sum(), out

    t0 = time.perf_counter()
    (_, out), grads = jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    jax.block_until_ready(grads)
    first_call_s = time.perf_counter() - t0
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(*f32)

    out, ref = np.asarray(out, np.float32), np.asarray(ref)
    need(bool(np.isfinite(out).all()), "flash output not finite")
    fwd_err = float(np.abs(out - ref).max())
    need(fwd_err <= KERNEL_FWD_ATOL,
         f"flash forward off by {fwd_err} > {KERNEL_FWD_ATOL}")
    grad_err = {}
    for name, g, r in zip("qkv", grads, ref_grads):
        g, r = np.asarray(g, np.float32), np.asarray(r)
        need(bool(np.isfinite(g).all()), f"d{name} not finite")
        grad_err[name] = float(np.abs(g - r).max() / np.abs(r).max())
        need(grad_err[name] <= KERNEL_GRAD_RTOL,
             f"flash d{name} off by {grad_err[name]} of the largest "
             f"reference entry > {KERNEL_GRAD_RTOL}")
    return {"shape": list(shape), "dtype": "bfloat16", "causal": True,
            "interpret": size.interpret, "fwd_max_abs_err": fwd_err,
            "fwd_atol": KERNEL_FWD_ATOL, "grad_rel_err": grad_err,
            "grad_rtol": KERNEL_GRAD_RTOL,
            "first_call_s": round(first_call_s, 3)}


def corpus(work: str, size: Size = REAL) -> str:
    """``<work>/corpus``: a token shard the model can learn from in a
    handful of steps — 2^19 draws from 512 of the vocabulary's ids. (The
    zoo's synthetic stream is uniform over the vocabulary: nothing to learn,
    so no loss to watch fall.) Made from the seed, once; numpy only."""
    import numpy as np

    directory = os.path.join(work, "corpus")
    if not os.path.exists(os.path.join(directory, "tokens-0.npy")):
        os.makedirs(directory, exist_ok=True)
        rng = np.random.default_rng(SEED)
        support = rng.choice(size.vocab, 512, replace=False)
        np.save(os.path.join(directory, "tokens-0.npy"),
                support[rng.integers(0, 512, 1 << 19)].astype(np.int32))
    return directory


def runner_cmd(size: Size, steps: int, ckpt_dir: str, data_dir: str) -> list:
    cmd = [sys.executable, "-m", "easydl_tpu.models.run", "--model", "gpt"]
    for key, val in size.model:
        cmd += ["--model-arg", f"{key}={json.dumps(val)}"]
    return cmd + ["--batch", str(size.batch), "--steps", str(steps),
                  "--ckpt-every", "3", "--ckpt-dir", ckpt_dir,
                  "--data-dir", data_dir]


def run_runner(size: Size, steps: int, work: str) -> dict:
    """One ``python -m easydl_tpu.models.run`` on the smoke's corpus and
    checkpoint directory; returns what its log says."""
    cmd = runner_cmd(size, steps, os.path.join(work, "ckpt"),
                     corpus(work, size))
    env = dict(os.environ, PYTHONPATH=HERE + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, env=env, cwd=HERE, capture_output=True,
                          text=True)
    log = proc.stderr
    with open(os.path.join(work, f"runner-steps{steps}.log"), "w") as f:
        f.write(log)
    need(proc.returncode == 0,
         f"runner exited {proc.returncode}: {log.strip()[-1500:]}")
    losses = dict(re.findall(r"step (\d+) loss ([-\w.]+)", log))
    losses = {step: float(x) for step, x in losses.items()}
    first = re.search(r"first step done: (\{.*\})", log)
    need(first is not None, "runner logged no compile summary")
    peak = re.search(r"peak device memory: (\d+) bytes", log)
    resumed = re.search(r"resumed from step (\d+)", log)
    return {"cmd": " ".join(cmd[1:]), "log": log, "losses": losses,
            "wall_s": round(time.perf_counter() - t0, 1),
            **json.loads(first.group(1)),
            "peak_hbm_bytes": int(peak.group(1)) if peak else None,
            "resumed_from": int(resumed.group(1)) if resumed else None}


def check_runner_log(size: Size, r: dict) -> None:
    log = r.pop("log")
    need(f"device: {size.platform} " in log,
         f"runner did not log device {size.platform}")
    need(size.attention_line in log,
         f"runner's log lacks {size.attention_line!r}")
    if size.platform == "tpu":
        need("reference path" not in log and "INTERPRETED" not in log,
             "runner logged a drop from the compiled kernel")
    need(finite(r["losses"].values()), f"non-finite loss: {r['losses']}")


def committed_steps(ckpt_dir: str) -> list:
    """Steps under ``ckpt_dir`` whose save finished (core/checkpoint.py
    writes ``step_<n>/COMMITTED`` last)."""
    try:
        names = os.listdir(ckpt_dir)
    except OSError:
        return []
    return sorted(int(n[len("step_"):]) for n in names
                  if n.startswith("step_") and os.path.exists(
                      os.path.join(ckpt_dir, n, "COMMITTED")))


def phase_train(size: Size = REAL, work: str = WORK) -> dict:
    """The zoo runner from scratch: 6 steps, checkpoints at 3 and 6."""
    r = run_runner(size, 6, work)
    check_runner_log(size, r)
    losses = r["losses"]
    need("1" in losses and "6" in losses,
         f"no loss for steps 1 and 6: {losses}")
    need(losses["6"] < losses["1"],
         f"loss did not fall: step 1 {losses['1']}, step 6 {losses['6']}")
    need(r["resumed_from"] is None, "fresh run claims to have resumed")
    need(6 in committed_steps(os.path.join(work, "ckpt")),
         "no committed checkpoint at step 6")
    return r


def phase_resume(train: dict, size: Size = REAL, work: str = WORK) -> dict:
    """The same command with ``--steps 9``: resumes from step 6, continues
    from the saved loss, finds the compile cache. ``train`` is the train
    phase's result."""
    r = run_runner(size, 9, work)
    check_runner_log(size, r)
    losses = r["losses"]
    need(r["resumed_from"] == 6,
         f"expected 'resumed from step 6', got {r['resumed_from']}")
    need("7" in losses and "9" in losses,
         f"no loss for steps 7 and 9: {losses}")
    r["warm_compile_s"] = r.pop("compile_s")
    # A fresh init would start from the first loss again; the restored
    # model starts where step 6 left off.
    first, last = train["losses"]["1"], train["losses"]["6"]
    need(losses["7"] < (first + last) / 2,
         f"step 7 loss {losses['7']} does not continue from step 6's "
         f"{last} (step 1 was {first})")
    if train["cache_misses"]:  # else the train phase was warm too
        r["cold_compile_s"] = train["compile_s"]
    # The step program must come from the cache: its answers must have saved
    # more compile time than this run spent. (Restoring compiles a little
    # that training from scratch never did, so a miss or two is no fault.)
    need(r["cache_saved_s"] > r["warm_compile_s"],
         f"resume compiled for {r['warm_compile_s']}s and the cache saved "
         f"it only {r['cache_saved_s']}s: the step was compiled again")
    need(9 in committed_steps(os.path.join(work, "ckpt")),
         "no committed checkpoint at step 9")
    return r


def _wait(cond, timeout: float, what: str):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.2)
    raise PhaseFailed(f"timed out after {timeout:.0f}s waiting for {what}")


def phase_elastic(size: Size = REAL, work: str = WORK,
                  timeout: float = 600.0, agent_platform: str = "") -> dict:
    """Master -> agent -> worker on the same model: run to a committed
    checkpoint, SIGKILL the worker, see the respawned one restore and pass
    the killed step. Master and agent live in THIS process and must never
    initialise a jax backend — the worker holds the chip.
    (``agent_platform``: what the agent is told its host has, where a test
    wants that to differ from what jax will find.)"""
    from easydl_tpu.elastic.agent import Agent
    from easydl_tpu.elastic.master import Master
    from easydl_tpu.elastic.timeline import read as _read_jsonl

    job = os.path.join(work, "elastic")
    shutil.rmtree(job, ignore_errors=True)
    os.makedirs(job)
    cfg = {"model": "gpt", "model_kwargs": dict(size.model),
           "global_batch": size.batch, "data_dir": corpus(work, size),
           "total_steps": 1_000_000, "ckpt_interval": 4, "seed": SEED}
    # standing_preflight makes the master hint the next generation while
    # this one trains — the hint an agent on an accelerator must decline,
    # because its own worker holds the device.
    master = Master(job_name="chip-smoke", workdir=job, desired_workers=1,
                    min_workers=1, worker_config=cfg,
                    standing_preflight=True,
                    prepare_min_uptime_s=0.0).start()
    agent_platform = agent_platform or size.platform
    agent = Agent("a0", master.address, job, slots=1,
                  platform=agent_platform).start()
    metrics_path = os.path.join(job, "metrics-a0.jsonl")
    timeline_path = os.path.join(job, "timeline-a0.jsonl")

    def worker_log():
        try:
            with open(os.path.join(job, "worker-a0.log"), errors="replace") as f:
                return f.read()
        except OSError:
            return ""

    def alive_or_fail():
        proc = agent._proc
        if proc is not None and proc.poll() not in (None, -signal.SIGKILL):
            raise PhaseFailed(f"worker exited {proc.poll()}: "
                              + worker_log().strip()[-1500:])

    try:
        t0 = time.monotonic()

        def trained_past_a_checkpoint():
            alive_or_fail()
            recs = _read_jsonl(metrics_path)
            done = max(committed_steps(os.path.join(job, "ckpt")), default=0)
            return done and recs and recs[-1]["step"] > done and (done, recs)

        ckpt_step, recs = _wait(trained_past_a_checkpoint, timeout,
                                "a committed checkpoint and a step past it")
        first_step_s = round(time.monotonic() - t0, 1)
        if agent_platform != "cpu":
            _wait(lambda: any(e["phase"] == "preflight_skipped"
                              for e in _read_jsonl(timeline_path)), 60.0,
                  "the agent to decline the standing preflight")
        last = _read_jsonl(metrics_path)[-1]
        killed_at_step, killed_gen = last["step"], last["generation"]
        t_kill = time.time()
        agent.kill_worker_hard()

        def recovered():
            alive_or_fail()
            recs = _read_jsonl(metrics_path)
            return (recs and recs[-1]["generation"] > killed_gen
                    and recs[-1]["step"] > killed_at_step and recs)

        recs = _wait(recovered, timeout, "the respawned worker to pass "
                     f"step {killed_at_step}")
        after = [r for r in recs if r["generation"] > killed_gen]
        recovery_s = round(after[0]["t"] - t_kill, 2)
        events = _read_jsonl(timeline_path)
        # One kill, one new generation: anything else is a reshape nobody
        # asked for (a worker crash, an agent wrongly declared lost).
        need(killed_gen == 1 and after[0]["generation"] == 2,
             f"expected generation 1 killed and generation 2 recovering, "
             f"got {killed_gen} and {after[0]['generation']}")
        restored = [e for e in events if e["phase"] == "restored"
                    and e["gen"] == 2]
        need(restored and restored[0]["step"] >= ckpt_step,
             f"generation 2 did not restore a checkpoint: {restored}")
        need(after[0]["step"] == restored[0]["step"] + 1,
             f"first step after recovery {after[0]['step']} does not follow "
             f"the restored step {restored[0]['step']}")
        spawns = [{k: e[k] for k in ("gen", "mode", "reason") if k in e}
                  for e in events if e["phase"] == "spawn"]
        log = worker_log()
        need(f"device: {size.platform} " in log,
             f"worker did not log device {size.platform}")
        need(size.attention_line in log,
             f"worker's log lacks {size.attention_line!r}")
        if agent_platform != "cpu":
            need(all(s["mode"] == "cold" for s in spawns)
                 and spawns[-1].get("reason") == "device_held",
                 f"expected cold spawns, the last for device_held: {spawns}")
        losses = [r["loss"] for r in recs]
        need(finite(losses), "non-finite loss in the elastic run")
    finally:
        agent.stop()
        master.stop()

    from jax._src import xla_bridge

    need(not xla_bridge.backends_are_initialized(),
         "master/agent process initialised a jax backend")
    return {"first_checkpointed_step_s": first_step_s,
            "checkpoint_step": ckpt_step, "killed_at_step": killed_at_step,
            "restored_step": restored[0]["step"],
            "recovered_to_step": recs[-1]["step"],
            "recovery_s": recovery_s, "spawns": spawns,
            "preflight_skipped": [e.get("reason") for e in events
                                  if e["phase"] == "preflight_skipped"],
            "first_loss": losses[0], "last_loss": losses[-1],
            "parent_backend_initialised": False}


def phase_mesh(size: Size = REAL, work: str = WORK) -> dict:
    """Four chips, one process: the same model for 3 steps at global batch
    32 on one device, on dp=4 and on fsdp=2 x tp=2 — losses agree, shards
    sit on four devices — then save under dp=4 and restore under
    fsdp=2 x tp=2, bit for bit. One state at a time: it fills a chip."""
    import jax
    import numpy as np
    import optax

    from easydl_tpu.core.checkpoint import CheckpointManager
    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.sharding import unbox
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model
    from easydl_tpu.utils.env import configure_compile_cache
    from easydl_tpu.utils.profiling import CompileWatch, peak_device_bytes

    configure_compile_cache()
    compiles = CompileWatch()
    devices = jax.devices()
    need(len(devices) >= 4, f"the mesh phase needs 4 devices, have "
                            f"{len(devices)}")
    need(devices[0].platform == size.platform,
         f"jax found no {size.platform}: {devices[0].platform}")
    bundle = get_model("gpt", **dict(size.model))
    data_dir = corpus(work, size)
    ckpt_dir = os.path.join(work, "mesh-ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    def batches():
        from easydl_tpu.data import TokenFileDataset

        return iter(TokenFileDataset(data_dir, batch_size=size.mesh_batch,
                                     seq_len=size.seq_len, seed=SEED))

    def trainer_on(spec: MeshSpec, accum: int = 1) -> Trainer:
        return Trainer(
            init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
            optimizer=optax.adamw(1e-3),
            config=TrainConfig(global_batch=size.mesh_batch,
                               grad_accum=accum, seed=SEED),
            mesh=build_mesh(spec, devices=devices[:spec.size]))

    def devices_of(tree) -> int:
        """Distinct devices the largest leaf's shards sit on, and whether
        those shards are really pieces (not four whole copies)."""
        leaf = max(jax.tree.leaves(tree), key=lambda x: x.size)
        shards = leaf.addressable_shards
        pieces = {s.index for s in shards}
        return len({s.device for s in shards}), len(pieces)

    def run(spec: MeshSpec, accum: int = 1, save: bool = False):
        trainer = trainer_on(spec, accum)
        state = trainer.init_state()
        placed = {"params": devices_of(unbox(state.params))}
        data, losses = batches(), []
        for _ in range(size.mesh_steps):
            batch = trainer.shard_batch(next(data))
            placed["batch"] = devices_of(batch)
            state, metrics = trainer.step_fn(state, batch)
            losses.append(float(metrics["loss"]))
        host_params = None
        if save:
            ckpt = CheckpointManager(ckpt_dir)
            ckpt.save(size.mesh_steps, state)
            ckpt.wait()
            host_params = jax.tree.map(np.asarray, unbox(state.params))
        del state, trainer
        return losses, placed, host_params

    runs = {}
    runs["one_device"] = run(MeshSpec(dp=1), accum=size.mesh_accum)[:2]
    dp_losses, dp_placed, saved = run(MeshSpec(dp=4), save=True)
    runs["dp=4"] = (dp_losses, dp_placed)
    runs["fsdp=2,tp=2"] = run(MeshSpec(fsdp=2, tp=2))[:2]

    ref = runs["one_device"][0]
    for name, (losses, _) in runs.items():
        need(finite(losses), f"{name}: non-finite loss {losses}")
        for a, b in zip(losses, ref):
            need(abs(a - b) <= MESH_LOSS_RTOL * abs(b),
                 f"{name} losses {losses} leave the one-device run's "
                 f"{ref} by more than {MESH_LOSS_RTOL}")
    need(runs["dp=4"][1]["batch"] == (4, 4),
         f"dp=4 batch shards on {runs['dp=4'][1]['batch']}, want 4 "
         "pieces on 4 devices")
    need(runs["fsdp=2,tp=2"][1]["params"] == (4, 4),
         f"fsdp=2,tp=2 parameter shards on "
         f"{runs['fsdp=2,tp=2'][1]['params']}, want 4 pieces on 4 devices")

    # save under dp=4 (above) -> restore under fsdp=2 x tp=2
    trainer = trainer_on(MeshSpec(fsdp=2, tp=2))
    state = trainer.restore_from(CheckpointManager(ckpt_dir))
    need(state.int_step == size.mesh_steps, "restored the wrong step")
    restored = unbox(state.params)
    need(devices_of(restored) == (4, 4), "restored parameters not sharded "
         "over four devices")
    same = jax.tree.map(lambda a, b: bool((np.asarray(a) == b).all()),
                        restored, saved)
    need(all(jax.tree.leaves(same)), "restored parameters differ from "
         "the saved ones")
    del state, trainer
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    return {"device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)},
            "global_batch": size.mesh_batch, "steps": size.mesh_steps,
            "losses": {k: v[0] for k, v in runs.items()},
            "loss_rtol": MESH_LOSS_RTOL,
            "devices_pieces": {k: v[1] for k, v in runs.items()},
            "reshard_restore": "dp=4 -> fsdp=2,tp=2 bit-identical",
            "peak_hbm_bytes": peak_device_bytes(), **compiles.summary()}


PHASES = {"device": phase_device, "kernel": phase_kernel,
          "train": phase_train, "resume": phase_resume,
          "elastic": phase_elastic, "mesh": phase_mesh}
#: per-phase wall-clock caps (the run's budget caps them further)
CAPS = {"device": 120, "kernel": 240, "train": 420, "resume": 360,
        "elastic": 600, "mesh": 1100}


# ------------------------------------------------------------ parent / child
def child_main(phase: str, train_json: str) -> int:
    """``--phase NAME``: run one phase, print its JSON line, exit 0/1."""
    args = [json.loads(train_json)] if phase == "resume" else []
    try:
        result = PHASES[phase](*args)
    except PhaseFailed as e:
        print(json.dumps({"phase": phase, "ok": False, "error": str(e)}),
              flush=True)
        return 1
    print(json.dumps({"phase": phase, "ok": True, **result}), flush=True)
    return 0


def run_phase(phase: str, deadline: float, extra: list) -> dict:
    """Run one phase as a child in its own process group; whatever it
    leaves behind is killed with the group. Returns its JSON line."""
    timeout = min(CAPS[phase], deadline - time.monotonic())
    if timeout <= 0:
        raise PhaseFailed(f"{phase}: no time left in the {BUDGET_S:.0f}s "
                          "budget")
    env = dict(os.environ, PYTHONPATH=HERE + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase] + extra,
        cwd=HERE, env=env, stdout=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        out = ""
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    result = None
    for line in out.splitlines():
        if line.startswith("{"):
            try:
                result = json.loads(line)
            except ValueError:
                continue
    if result is None or result.get("phase") != phase:
        raise PhaseFailed(f"{phase}: child exited {proc.returncode} with "
                          f"no result (cap {timeout:.0f}s)")
    result["phase_wall_s"] = round(time.monotonic() - t0, 1)
    print(json.dumps(result), flush=True)
    if proc.returncode != 0 or not result.get("ok"):
        raise PhaseFailed(f"{phase}: {result.get('error', 'failed')}")
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: run only the mesh phase (one device "
                         "vs dp=4 vs fsdp=2 x tp=2, reshard-restore)")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--train-json", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return child_main(args.phase, args.train_json)

    if not os.path.isdir(os.path.join(HERE, "easydl_tpu")):
        print("chip_smoke.py: no easydl_tpu/ beside this script — it drives "
              "the repository, and is nothing without it", file=sys.stderr)
        return 2
    deadline = time.monotonic() + BUDGET_S
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    try:
        if args.mesh:
            device = run_phase("mesh", deadline, [])["device"]
            want = 4
        else:
            device = run_phase("device", deadline, [])
            device = {k: device[k] for k in ("platform", "kind", "count")}
            run_phase("kernel", deadline, [])
            train = run_phase("train", deadline, [])
            run_phase("resume", deadline, ["--train-json", json.dumps(
                {k: train[k] for k in ("losses", "compile_s",
                                       "cache_misses")})])
            shutil.rmtree(os.path.join(WORK, "ckpt"), ignore_errors=True)
            run_phase("elastic", deadline, [])
            want = 1
        if device["platform"] != "tpu" or device["count"] != want:
            raise PhaseFailed(f"ran on {device}, want {want} tpu device(s)")
    except PhaseFailed as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

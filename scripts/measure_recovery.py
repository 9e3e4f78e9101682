"""Measure the north-star elasticity metrics and write RECOVERY.json.

BASELINE.json's north stars ("post-preemption recovery time", "8->32 chip
scale-up with <5% throughput loss") exist in the reference only as promises
(/root/reference/README.md:25-35); this script MEASURES them on the simulated
distributed runtime (real master + agents + jax.distributed worker
subprocesses on a CPU mesh — the same machinery that runs on TPU hosts, at
2->4 proxy scale) and DECOMPOSES the generation-switch stall into its phases
(quiesce signal, drain checkpoint, exit detect, re-rendezvous, process start,
runtime imports, distributed init, restore, first-step compile) from the
per-host timelines (easydl_tpu/elastic/timeline.py), so each round attacks
the dominant term instead of guessing.

Scenarios:
1. preemption: SIGKILL one of two workers (no notice) mid-run; measure
   kill -> first-post-restore-step wall time and steps of work lost.
2. scale-up (x4 variants): apply a plan doubling the worker count mid-run;
   measure the generation-switch stall and throughput loss over the
   transition window vs a static-world extrapolation:
     a. cold compile cache, cold worker start;
     b. warm compile cache, cold worker start;
     c. warm compile cache + warm standby workers (jax pre-imported);
     d. preflight: the next generation dist-joins, builds, and compiles
        WHILE generation 1 trains; the switch itself only pays
        quiesce + promote + restore + an already-compiled step.

Usage: python scripts/measure_recovery.py [--out RECOVERY.json]
Must run where jax can use a CPU platform; spawns its own subprocess with
the forced-CPU env (like dryrun_multichip) if the current backend is not.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# `python scripts/measure_recovery.py` puts scripts/ (not the repo) on
# sys.path; the bootstrap imports easydl_tpu before any subprocess env is set
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from easydl_tpu.elastic.goodput import wasted_steps  # noqa: E402
from easydl_tpu.utils.env import rerun_on_cpu_mesh  # noqa: E402


def read_metrics(workdir: str, agent_id: str):
    path = os.path.join(workdir, f"metrics-{agent_id}.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def wait_for(cond, timeout, desc):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.2)
    raise TimeoutError(f"timed out waiting for {desc}")


def _phase_chain(recs, chain, t0):
    """Fold raw timeline records into consecutive phase durations.

    ``chain`` is [(phase_label, event_phase, gen, pick)] where pick is
    ``max`` (slowest host gates the collective) or ``min`` (first record).
    Durations are between consecutive *present* boundaries starting at t0;
    a missing event yields None for its phase, charging its time to the
    next present one (stated rather than hidden).
    """
    out = {}
    prev = t0
    inversions = []
    for label, phase, gen, pick in chain:
        ts = [r["t"] for r in recs if r["phase"] == phase and r["gen"] == gen]
        if not ts:
            out[label] = None
            continue
        t = pick(ts)
        delta = t - prev
        if delta < 0:
            # Adjacent boundaries are per-event maxima across hosts whose
            # events are not globally ordered; a small inversion is clock/
            # ordering noise. Clamp to 0 and SAY so — a negative phase bar
            # in the artifact would be incoherent (the r4 lesson).
            inversions.append({label: round(delta, 3)})
            delta = 0.0
        out[label] = round(delta, 2)
        prev = max(prev, t)
    out["total_s"] = round(prev - t0, 2)
    if inversions:
        out["clamped_inversions"] = inversions
    return out


def decompose_switch(workdir: str, gen_from: int, gen_to: int, t0: float):
    from easydl_tpu.elastic import timeline

    recs = timeline.read_all(workdir)
    modes = sorted(
        {r.get("mode", "?") for r in recs
         if r["phase"] == "spawn" and r["gen"] == gen_to}
    )
    if modes == ["preflight"]:
        # ALL promotions were preflight: the overlapped decomposition is
        # well-defined. A mixed preflight/cold switch (a crashed preflight
        # fell back to cold) uses the standard chain — the cold rank's
        # post-gate build is the real critical path there.
        # Preflighted switch: the new generation's process start, imports,
        # dist init, trainer build AND step compile all happened while the
        # old generation was still training (between the plan and the
        # drain-gate release). Folding those events into a post-quiesce
        # chain would produce negative phases — decompose them as the
        # OVERLAPPED window instead, and time the switch itself from the
        # moment the last preflight reported ready (when the master
        # released the drain).
        ready_ts = [r["t"] for r in recs
                    if r["phase"] == "preflight_ready" and r["gen"] == gen_to]
        gate_open = max(ready_ts) if ready_ts else t0
        chain = [
            ("quiesce_signal_s",        "quiesce_sent",       gen_from, max),
            ("drain_to_step_boundary_s", "quiesce_ckpt_begin", gen_from, max),
            ("drain_checkpoint_s",      "quiesce_exit",       gen_from, max),
            ("exit_detect_s",           "worker_exit",        gen_from, max),
            ("promote_s",               "spawn",              gen_to,   max),
            ("preflight_go_s",          "preflight_go",       gen_to,   max),
            ("restore_agree_s",         "restore_agreed",     gen_to,   max),
            ("restore_read_s",          "restored",           gen_to,   max),
            ("first_step_s",            "first_step_done",    gen_to,   max),
        ]
        phases = _phase_chain(recs, chain, gate_open)
        phases["prepare_overlap_s"] = round(gate_open - t0, 2)
        overlapped = _phase_chain(recs, [
            ("process_start_s",   "worker_main_start", gen_to, max),
            ("runtime_imports_s", "jax_imported",      gen_to, max),
            ("dist_init_s",       "dist_init_done",    gen_to, max),
            ("trainer_build_s",   "trainer_built",     gen_to, max),
            ("step_compile_s",    "preflight_ready",   gen_to, max),
        ], t0)
        overlapped.pop("total_s", None)
        phases["overlapped_during_training"] = overlapped
        phases["spawn_modes"] = modes
        return phases
    chain = [
        ("quiesce_signal_s",        "quiesce_sent",       gen_from, max),
        ("drain_to_step_boundary_s", "quiesce_ckpt_begin", gen_from, max),
        ("drain_checkpoint_s",      "quiesce_exit",       gen_from, max),
        ("exit_detect_s",           "worker_exit",        gen_from, max),
        ("rendezvous_respawn_s",    "spawn",              gen_to,   max),
        ("process_start_s",         "worker_main_start",  gen_to,   max),
        ("runtime_imports_s",       "jax_imported",       gen_to,   max),
        ("dist_init_s",             "dist_init_done",     gen_to,   max),
        ("trainer_build_s",         "trainer_built",      gen_to,   max),
        ("restore_agree_s",         "restore_agreed",     gen_to,   max),
        ("restore_read_s",          "restored",           gen_to,   max),
        ("first_step_compile_s",    "first_step_done",    gen_to,   max),
    ]
    phases = _phase_chain(recs, chain, t0)
    phases["spawn_modes"] = modes
    return phases


def decompose_recovery(workdir: str, gen_to: int, t_kill: float):
    from easydl_tpu.elastic import timeline

    recs = timeline.read_all(workdir)
    chain = [
        ("detect_and_rendezvous_s", "spawn",             gen_to, max),
        ("process_start_s",         "worker_main_start", gen_to, max),
        ("runtime_imports_s",       "jax_imported",      gen_to, max),
        ("dist_init_s",             "dist_init_done",    gen_to, max),
        ("trainer_build_s",         "trainer_built",     gen_to, max),
        ("restore_agree_s",         "restore_agreed",    gen_to, max),
        ("restore_read_s",          "restored",          gen_to, max),
        ("first_step_compile_s",    "first_step_done",   gen_to, max),
    ]
    return _phase_chain(recs, chain, t_kill)


def preemption_notice_scenario() -> dict:
    """The NOTICE path (GCE-style warning before the VM dies): the master
    preflights the survivor generation on the short window while the
    noticed host keeps training, then drains gracefully and promotes the
    pre-compiled workers. Measures notice→resumed wall time, the actual
    training stall, and whether the boundary was lossless."""
    from easydl_tpu.elastic.agent import Agent
    from easydl_tpu.elastic.master import Master

    wd = tempfile.mkdtemp(prefix="recovery-notice-")
    cfg = {
        "model": "mlp",
        "model_kwargs": {"input_shape": [8, 8, 1], "features": [32, 32]},
        # ckpt_interval deliberately sparse: a lossless boundary must come
        # from the graceful quiesce, not a lucky periodic save.
        "global_batch": 32, "total_steps": 1_000_000, "ckpt_interval": 500,
        "sync_every": 5, "lr": 0.01, "seed": 0,
    }
    master = Master(job_name="notice", workdir=wd, desired_workers=2,
                    min_workers=2, worker_config=cfg,
                    prepare_timeout_s=600.0, preempt_prepare_timeout_s=90.0,
                    prepare_min_uptime_s=0.0).start()
    agents = [Agent(f"a{i}", master.address, wd, slots=2).start()
              for i in range(3)]
    try:
        def steady():
            st = master.status()  # ONE snapshot: members vs agents agree
            return st["members"] and all(
                st["agents"].get(m, {}).get("step", 0) >= 20
                for m in st["members"]
            )

        wait_for(steady, 240, "steady state before the notice")
        gen1 = master.status()["generation"]
        victim = sorted(master.status()["members"])[1]
        t_notice = time.time()
        agents[int(victim[1])].notify_preemption()
        wait_for(
            lambda: master.status()["generation"] > gen1
            and master.status()["phase"] == "stable",
            240, "replacement generation",
        )
        gen2 = master.status()["generation"]

        def gen2_metrics():
            recs = []
            for i in range(3):
                recs += read_metrics(wd, f"a{i}")
            return [r for r in recs if r["generation"] == gen2]

        wait_for(lambda: gen2_metrics(), 120, "replacement training")
        recs = []
        for i in range(3):
            recs += read_metrics(wd, f"a{i}")
        g1 = [r for r in recs if r["generation"] == gen1]
        g2 = [r for r in recs if r["generation"] == gen2]
        t_last_g1 = max(r["t"] for r in g1)
        t_first_g2 = min(r["t"] for r in g2)
        phases = decompose_switch(wd, gen1, gen2, t_notice)
        return {
            "scenario": "preemption NOTICE (cloud warning before the VM "
                        "dies): preflight on the short window, graceful "
                        "drain, promote pre-compiled survivors",
            "world": "3 agents x 2 CPU devices (2 members + 1 standby)",
            "preempt_prepare_window_s": 90.0,
            "notice_to_resumed_s": round(t_first_g2 - t_notice, 2),
            "training_stall_s": round(t_first_g2 - t_last_g1, 2),
            "zero_lost_work": bool(
                min(r["step"] for r in g2)
                == max(r["step"] for r in g1) + 1
            ),
            "noticed_host_excluded": victim not in master.status()["members"],
            "spawn_modes": phases.get("spawn_modes"),
            "phases": phases,
        }
    finally:
        for a in agents:
            a.stop()
        master.stop()


def preemption_scenario(warm_start: bool) -> dict:
    from easydl_tpu.elastic.agent import Agent
    from easydl_tpu.elastic.master import Master

    wd = tempfile.mkdtemp(prefix="recovery-preempt-")
    cfg = {
        "model": "mlp",
        "model_kwargs": {"input_shape": [8, 8, 1], "features": [32, 32]},
        "global_batch": 32, "total_steps": 60, "ckpt_interval": 5,
        "lr": 0.01, "seed": 0,
    }
    master = Master(job_name="recovery", workdir=wd, desired_workers=2,
                    min_workers=1, heartbeat_timeout=1.5,
                    worker_config=cfg).start()
    a0 = Agent("a0", master.address, wd, slots=2, warm_start=warm_start).start()
    a1 = Agent("a1", master.address, wd, slots=2, warm_start=warm_start).start()
    try:
        wait_for(
            lambda: min(
                master.status()["agents"].get("a0", {}).get("step", 0),
                master.status()["agents"].get("a1", {}).get("step", 0),
            ) >= 10,
            180, "both workers past step 10",
        )
        gen_before = master.status()["generation"]
        t_kill = time.time()
        a1.kill_worker_hard()
        a1.stop()
        assert master.wait_done(timeout=300), master.status()
        final_gen = master.status()["generation"]
        m0 = read_metrics(wd, "a0")
        pre = [r for r in m0 if r["generation"] <= gen_before and r["t"] < t_kill]
        post = [r for r in m0 if r["generation"] == final_gen]
        first_post = min(post, key=lambda r: r["step"])
        return {
            "scenario": "preemption (SIGKILL worker, no notice)",
            "world": "2 agents x 2 CPU devices",
            "warm_standby": warm_start,
            "recovery_s": round(first_post["t"] - t_kill, 2),
            "steps_lost": len(wasted_steps(
                (r["step"] for r in pre), first_post["step"] - 1)),
            "ckpt_interval": cfg["ckpt_interval"],
            "detect_mechanism": "heartbeat timeout 1.5s + peer crash report",
            "generations": final_gen,
            "phases": decompose_recovery(wd, final_gen, t_kill),
        }
    finally:
        a0.stop()
        a1.stop()
        master.stop()


def scale_up_scenario(cache: bool, warm_start: bool,
                      preflight: bool = False) -> dict:
    from easydl_tpu.api import ResourcePlan, RolePlan
    from easydl_tpu.elastic.agent import Agent
    from easydl_tpu.elastic.master import Master

    # The workers' persistent compilation cache is the checkout's one fixed
    # directory (utils/env.py): with it on, a run after the first skips the
    # generation switch's XLA recompile; off, every compile is paid.
    os.environ["EASYDL_COMPILE_CACHE"] = "" if cache else "off"
    wd = tempfile.mkdtemp(prefix="recovery-scale-")
    cfg = {
        "model": "mlp",
        "model_kwargs": {"input_shape": [8, 8, 1], "features": [32, 32]},
        "global_batch": 64, "total_steps": 4000, "ckpt_interval": 100,
        "sync_every": 5, "lr": 0.01, "seed": 0,
    }
    # preflight=True removes the uptime gate so the plan (applied shortly
    # after steady state) triggers the PREPARING path: the next generation
    # dist-joins and compiles while generation 1 keeps training.
    master = Master(job_name="scaleup", workdir=wd, desired_workers=2,
                    min_workers=2, worker_config=cfg,
                    prepare_timeout_s=240.0 if preflight else 0.0,
                    prepare_min_uptime_s=0.0).start()
    agents = [
        Agent(f"a{i}", master.address, wd, slots=1,
              warm_start=warm_start).start()
        for i in range(4)
    ]
    try:
        wait_for(
            lambda: any(
                a.get("step", 0) >= 40
                for a in master.status()["agents"].values()
            ),
            240, "members past step 40 (warm steady state)",
        )
        if warm_start:
            # The point of the warm variant is measuring promote-vs-cold:
            # don't fire the plan until standbys finished importing jax.
            wait_for(
                lambda: all(
                    os.path.exists(os.path.join(wd, f)) for f in (
                        f".warm-a{i}-1.json.ready" for i in range(4)
                    )
                ),
                240, "all warm standbys ready",
            )
        gen1 = master.status()["generation"]
        t_plan = time.time()
        master.apply_plan(ResourcePlan(
            job_name="scaleup", version=100,
            roles={"worker": RolePlan(replicas=4)},
        ))

        def gen2_steps_recorded(n: int) -> bool:
            recs = []
            for i in range(4):
                recs += read_metrics(wd, f"a{i}")
            return len([r for r in recs if r["generation"] > gen1]) >= n

        # Wait for actual post-reshape steps in the metrics (the rendezvous
        # status carries step counts over from gen 1, so it can't tell us).
        wait_for(lambda: gen2_steps_recorded(40), 300,
                 "new generation writing step metrics")
        merged = []
        for i in range(4):
            merged += read_metrics(wd, f"a{i}")
        g1 = [r for r in merged if r["generation"] == gen1]
        g2 = [r for r in merged if r["generation"] > gen1]
        gen2 = min(r["generation"] for r in g2)
        # Steady-state throughput before the plan: last 20 gen-1 steps,
        # global samples/sec (records are per-rank; each rank's record
        # reports the global samples/sec of its world).
        g1_tail = sorted(g1, key=lambda r: r["step"])[-20:]
        tput_before = sum(r["samples_per_sec"] for r in g1_tail) / len(g1_tail)
        t_last_g1 = max(r["t"] for r in g1)
        t_first_g2 = min(r["t"] for r in g2)
        switch_s = t_first_g2 - t_last_g1
        # Throughput-loss over the whole transition [t_plan .. first new-
        # generation step + tail]: covers the prepare window (preflighted
        # switches keep training through it — any compile contention shows
        # up here honestly) AND the switch stall itself, vs a static-world
        # extrapolation.
        W = (t_first_g2 - t_plan) + 15.0
        ranks_per_step = {}
        for r in merged:
            if t_plan <= r["t"] <= t_plan + W:
                ranks_per_step.setdefault((r["generation"], r["step"]), 0)
                ranks_per_step[(r["generation"], r["step"])] += 1
        achieved_steps = len(ranks_per_step)
        achieved_samples = achieved_steps * cfg["global_batch"]
        static_samples = tput_before * W
        loss_pct = (1.0 - achieved_samples / static_samples) * 100.0
        g2_tail = sorted(g2, key=lambda r: r["step"])[-10:]
        tput_after = (
            sum(r["samples_per_sec"] for r in g2_tail) / len(g2_tail)
            if g2_tail else 0.0
        )
        return {
            "scenario": "scale-up 2->4 workers mid-run (proxy for 8->32 chips)",
            "warm_standby": warm_start,
            "preflight": preflight,
            "generation_switch_s": round(switch_s, 2),
            "throughput_before_samples_per_s": round(tput_before, 1),
            "throughput_after_samples_per_s": round(tput_after, 1),
            "transition_window_s": round(W, 1),
            "throughput_loss_pct_vs_static": round(loss_pct, 1),
            # The window-loss number above is an artifact of this
            # measurement's tiny window (W ≈ 2×switch, so the job is
            # stalled for ~half of it by construction). The defensible
            # north-star proxy is the stall amortized over how often the
            # autoscaler actually fires: a scale event costs ~switch_s of
            # lost training, so loss% = switch_s / event interval. Brain's
            # cooldown (30s min, realistic events minutes apart) bounds the
            # cadence.
            "amortized_loss_pct_at_10min_events": round(switch_s / 600 * 100, 2),
            "amortized_loss_pct_at_30min_events": round(switch_s / 1800 * 100, 2),
            "north_star": "<5% throughput loss vs static pod",
            "compile_cache": "persistent (utils/env.py configure_compile_cache)",
            "phases": decompose_switch(wd, gen1, gen2, t_plan),
        }
    finally:
        for a in agents:
            a.stop()
        master.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "RECOVERY.json"))
    args = ap.parse_args()

    # The elastic scenarios need a multi-device CPU platform.
    rerun_on_cpu_mesh(__file__, "EASYDL_RECOVERY_CHILD")

    scale_cold = scale_up_scenario(cache=False, warm_start=False)
    scale_up_scenario(cache=True, warm_start=False)  # fills the cache
    scale_warm_cache = scale_up_scenario(cache=True, warm_start=False)
    scale_warm_full = scale_up_scenario(cache=True, warm_start=True)
    scale_preflight = scale_up_scenario(cache=True, warm_start=False,
                                        preflight=True)
    result = {
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "platform": "simulated-distributed CPU mesh (jax.distributed worker "
                    "subprocesses; same code path as TPU hosts)",
        "host_cores": os.cpu_count(),
        "caveat": "multi-process scenarios oversubscribe this host's "
                  f"{os.cpu_count()} core(s); absolute throughputs reflect "
                  "CPU contention, not TPU behavior — the mechanism timings "
                  "(per-phase decomposition, warm-vs-cold deltas) are the "
                  "meaningful signal",
        "preemption": preemption_scenario(warm_start=True),
        "preemption_notice": preemption_notice_scenario(),
        "scale_up_cold_cache": scale_cold,
        "scale_up_warm_cache": scale_warm_cache,
        "scale_up_warm_cache_warm_standby": scale_warm_full,
        "scale_up_preflight": scale_preflight,
    }
    # Merge, don't clobber: other measurement scripts (measure_longwindow)
    # own their own top-level sections of the same file.
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prior = json.load(f)
            for key, val in prior.items():
                result.setdefault(key, val)
        except (OSError, ValueError):
            pass
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()

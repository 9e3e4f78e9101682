#!/usr/bin/env python3
"""Measure elastic-event cost over a LONG window — a measurement, not a
projection.

PARITY.md's north-star status was amortizing the measured generation-switch
cost over an *assumed* event cadence (the round-3 advisor flagged it). This
script measures it: two runs of identical wall length and steady-state
world size —

- **baseline**: 2 workers, no events;
- **elastic**: 2 workers, a SIGKILL preemption injected every
  ``--event-every`` seconds (the failure → heartbeat-detect → re-rendezvous
  → reshard-restore path, i.e. the same machinery a scale event exercises,
  at a world size whose steady-state throughput matches the baseline's so
  the comparison isolates the event cost);

then reports the measured throughput loss at the tested cadence and the
per-event cost, from which the loss at any cadence follows by linear
amortization of a *measured* quantity.

Writes/merges a ``long_window`` section into RECOVERY.json (``--out``).

Usage (forced-CPU env, like measure_recovery.py):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=/root/repo python scripts/measure_longwindow.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def read_metrics(workdir, agent_id):
    path = os.path.join(workdir, f"metrics-{agent_id}.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def samples_in_window(workdir, agents, t0, t1, global_batch):
    """Steps completed inside [t0, t1] across the job.

    Keyed by the job-level step ALONE: after a preemption the restored
    generation replays the steps between the last checkpoint and the kill,
    and counting those replays as fresh progress (e.g. keying by
    (generation, step)) would bias the elastic run's throughput optimistic
    by ~ckpt_interval/2 steps per event."""
    seen = set()
    for a in agents:
        for r in read_metrics(workdir, a):
            if t0 <= r["t"] <= t1:
                seen.add(r["step"])
    return len(seen) * global_batch


def run_window(window_s, event_every):
    from easydl_tpu.elastic.agent import Agent
    from easydl_tpu.elastic.master import Master

    wd = tempfile.mkdtemp(prefix="longwindow-")
    cfg = {
        "model": "mlp",
        "model_kwargs": {"input_shape": [8, 8, 1], "features": [32, 32]},
        "global_batch": 64, "total_steps": 10_000_000,
        # Auto cadence: bound work-at-risk by wall clock (~2s) instead of a
        # fixed step count — with the switch itself fast, replayed steps
        # between the last save and the kill are the avoidable loss.
        "ckpt_interval": "auto", "ckpt_target_s": 2.0,
        "lr": 0.01, "seed": 0,
    }
    master = Master(job_name="lw", workdir=wd, desired_workers=2,
                    min_workers=1, heartbeat_timeout=1.5,
                    worker_config=cfg).start()
    # warm_start: the production recovery posture (the preemption scenario
    # measures with it; the long window should exercise the same machinery)
    agents = [Agent(f"a{i}", master.address, wd, slots=2,
                    warm_start=True).start()
              for i in range(2)]
    events = 0
    try:
        # steady state before the window opens
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            steps = [a.get("step", 0)
                     for a in master.status()["agents"].values()]
            if steps and min(steps) >= 20:
                break
            time.sleep(0.5)
        else:
            raise TimeoutError("never reached steady state")
        t0 = time.time()
        t_end = t0 + window_s
        next_event = t0 + event_every if event_every else float("inf")
        victim = 1
        while time.time() < t_end:
            if time.time() >= next_event:
                agents[victim].kill_worker_hard()
                events += 1
                victim = 1 - victim
                next_event += event_every
            time.sleep(0.5)
        t1 = time.time()
        samples = samples_in_window(wd, [f"a{i}" for i in range(2)],
                                    t0, t1, cfg["global_batch"])
        return samples, t1 - t0, events
    finally:
        for a in agents:
            a.stop()
        master.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--window", type=float, default=360.0)
    ap.add_argument("--event-every", type=float, default=90.0)
    ap.add_argument("--out", default=os.path.join(REPO, "RECOVERY.json"))
    args = ap.parse_args()

    # Both windows' workers share the checkout's persistent compile cache
    # (utils/env.py configure_compile_cache).
    base_samples, base_dt, _ = run_window(args.window, 0.0)
    el_samples, el_dt, events = run_window(args.window, args.event_every)
    base_rate = base_samples / base_dt
    el_rate = el_samples / el_dt
    loss_pct = 100.0 * (1.0 - el_rate / base_rate)
    per_event_s = ((base_rate - el_rate) * el_dt / base_rate / events
                   if events else 0.0)
    section = {
        "scenario": f"{args.window:.0f}s window, SIGKILL preemption every "
                    f"{args.event_every:.0f}s vs identical static run "
                    "(same steady-state world: isolates the event cost)",
        "events": events,
        "baseline_samples_per_s": round(base_rate, 1),
        "elastic_samples_per_s": round(el_rate, 1),
        "measured_loss_pct_at_tested_cadence": round(loss_pct, 2),
        "equivalent_stall_per_event_s": round(per_event_s, 2),
        "loss_pct_at_10min_events": round(
            100.0 * per_event_s / 600.0, 2),
        "loss_pct_at_30min_events": round(
            100.0 * per_event_s / 1800.0, 2),
        "note": "10/30-min numbers amortize the MEASURED per-event stall "
                "(not an assumed switch time) over those cadences",
    }
    doc = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            doc = json.load(f)
    doc["long_window"] = section
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(section, indent=2))


if __name__ == "__main__":
    main()

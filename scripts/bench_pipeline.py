#!/usr/bin/env python3
"""Measure the pipeline schedule: overhead vs pure DP, bubble vs microbatches.

This runs the same model under (a) pure dp=8 and (b) dp=4 × pp=2 at
several microbatch counts on the forced-CPU 8-device mesh (it exercises
the compiled schedule, not the hardware: its times are CPU times), and
reports step times plus the analytic bubble fraction each config
predicts (ops/pipeline.bubble_fraction) so the measured trend can be
checked against the model.

Self-bootstrapping into a forced-CPU child like the other measurement
scripts; writes/merges a ``pipeline`` section into --out (PROFILE.json
by default, next to the attribution evidence).

Usage: python scripts/bench_pipeline.py [--steps 8] [--out PROFILE.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from easydl_tpu.utils.env import knob_raw  # noqa: E402


def measure(steps: int) -> dict:
    import jax
    import numpy as np
    import optax

    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.sharding import DEFAULT_RULES
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model
    from easydl_tpu.ops.pipeline import (bubble_fraction, make_pipeline,
                                         pipeline_rules)

    # Big enough for schedule signal on CPU, small enough to compile fast.
    common = dict(size="test", seq_len=64, vocab=512, dtype="float32")
    global_batch = 32

    def run(label, bundle, spec, mesh=None, rules=None):
        kwargs = {"mesh": mesh} if mesh is not None else {"mesh_spec": spec}
        cfg_kwargs = {"global_batch": global_batch}
        if rules is not None:
            cfg_kwargs["rules"] = rules
        trainer = Trainer(
            init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
            optimizer=optax.adamw(1e-3),
            config=TrainConfig(**cfg_kwargs), **kwargs,
        )
        state = trainer.init_state()
        data = iter(bundle.make_data(global_batch))
        for _ in range(2):  # compile + warm
            state, m = trainer.train_step(state, next(data))
        float(jax.device_get(m["loss"]))
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = trainer.train_step(state, next(data))
        loss = float(jax.device_get(m["loss"]))
        dt = (time.perf_counter() - t0) / steps
        assert np.isfinite(loss)
        return {"config": label, "step_time_s": round(dt, 4),
                "loss": round(loss, 4)}

    results = []
    control = run("dp=8 (no pipeline)", get_model("gpt", **common),
                  MeshSpec(dp=8))
    results.append(control)

    pp_mesh = build_mesh(MeshSpec(dp=4, pp=2))
    for m in (2, 4, 8):
        bundle = get_model(
            "gpt", **common,
            pipeline_fn=make_pipeline(pp_mesh, microbatches=m),
            pipeline_stages=2,
        )
        rec = run(f"dp=4 x pp=2, microbatches={m}", bundle,
                  MeshSpec(dp=4, pp=2), mesh=pp_mesh,
                  rules=pipeline_rules(DEFAULT_RULES))
        rec["bubble_fraction_model"] = round(bubble_fraction(m, 2), 3)
        rec["vs_control"] = round(
            rec["step_time_s"] / control["step_time_s"], 3)
        results.append(rec)
    return {
        "platform": f"{jax.default_backend()} x {jax.device_count()} "
                    "(forced-CPU mesh)",
        "model": "gpt test-size seq64",
        "global_batch": global_batch,
        "steps_timed": steps,
        "results": results,
        "note": "pp=2 halves per-device layer count but adds the fill-"
                "drain bubble + ppermute hops; the microbatch sweep "
                "checks the measured trend against the analytic "
                "(pp-1)/(m+pp-1) bubble model",
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--out", default=os.path.join(REPO, "PROFILE.json"))
    args = ap.parse_args()

    if knob_raw("EASYDL_PIPEBENCH_CHILD") != "1":
        import subprocess

        from easydl_tpu.utils.env import cpu_subprocess_env

        env = cpu_subprocess_env(8)
        env["EASYDL_PIPEBENCH_CHILD"] = "1"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--steps", str(args.steps), "--out", args.out],
            env=env, cwd=REPO, timeout=1800,
        )
        raise SystemExit(proc.returncode)

    section = measure(args.steps)
    doc = {}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
    doc["pipeline"] = section
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(section, indent=2))


if __name__ == "__main__":
    main()

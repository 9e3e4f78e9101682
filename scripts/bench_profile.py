#!/usr/bin/env python3
"""Record an XLA trace at the bench config and attribute step time.

MFU sat at ~0.507 across rounds while the attack was lever-guessing — this
script replaces guesses with a measured breakdown. It runs bench.py's exact
flagship config (GPT-2 345M, seq 1024, bf16, remat=dots, flash attention)
for a few steady-state steps under
``jax.profiler.trace`` (utils/profiling.py), then parses the Chrome-trace
JSON the profiler writes and aggregates TPU-lane op time by category:
flash fwd/bwd custom-calls, matmul fusions, other fusions, collectives,
infeed/outfeed, and gaps (host-bound time between device ops).

Output: one JSON report (``--out``, default PROFILE.json) with per-category
totals per step and the top-N individual ops — the evidence that names the
binding term.

Usage: python scripts/bench_profile.py [--steps 3] [--out PROFILE.json]
Needs the TPU: where jax finds none it exits non-zero and writes nothing.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def load_trace(logdir: str) -> dict:
    paths = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.trace.json.gz"))
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(REPO, "PROFILE.json"))
    ap.add_argument("--logdir", default="")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()

    import jax
    import optax

    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model
    from easydl_tpu.utils.profiling import trace

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench_profile.py traces the TPU; jax found {platform!r}. "
            "Nothing written.")
    n_chips = jax.device_count()
    size, seq_len = "345m", 1024
    grad_accum, global_batch = 32, 256 * n_chips
    bundle = get_model("gpt", size=size, seq_len=seq_len, remat=True,
                       remat_policy="dots", dtype="bfloat16",
                       fused_loss=False)

    trainer = Trainer(
        init_fn=bundle.init_fn,
        loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(2e-4, weight_decay=0.01),
        config=TrainConfig(global_batch=global_batch, grad_accum=grad_accum),
        mesh_spec=MeshSpec(dp=n_chips),
    )
    state = trainer.init_state()
    data = iter(bundle.make_data(global_batch))

    for _ in range(2):  # compile + warm
        state, metrics = trainer.train_step(state, next(data))
    jax.block_until_ready(metrics)

    logdir = args.logdir or tempfile.mkdtemp(prefix="bench-profile-")
    t0 = time.perf_counter()
    with trace(logdir):
        for _ in range(args.steps):
            state, metrics = trainer.train_step(state, next(data))
        jax.block_until_ready(metrics)
    wall = time.perf_counter() - t0

    from easydl_tpu.utils.profiling import attribute_trace

    attribution = attribute_trace(load_trace(logdir), top=args.top)
    busy_us = attribution.get("lane_busy_us", 0.0)
    report = {
        "config": f"gpt-{size} seq{seq_len} b{global_batch}/a{grad_accum} "
                  f"({platform}, {n_chips} chip)",
        "profiled_steps": args.steps,
        "wall_s": round(wall, 3),
        "wall_per_step_s": round(wall / args.steps, 4),
        # The busiest device lane's covered time is the honest per-step
        # device cost (trace collection inflates WALL time; the lane union
        # does not lie). Categories are SELF times on that lane and sum to
        # it by construction; the invariants block would flag any
        # regression.
        "device_busy_per_step_s": round(busy_us / 1e6 / args.steps, 4),
        "category_us_per_step": {
            k: round(v / args.steps, 1)
            for k, v in attribution.get("category_self_us", {}).items()
        },
        "top_ops_us_per_step": [
            {**o, "us": round(o["us"] / args.steps, 1)}
            for o in attribution.get("top_ops_self_us", [])
        ],
        "attribution": attribution,
        "trace_logdir": logdir,
    }
    # Merge, don't clobber: other sections of the same file (pipeline
    # numbers, superseded-history notes) belong to other writers — update
    # the loaded document with this report's keys, preserving the rest.
    doc = {}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
    doc.update(report)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()

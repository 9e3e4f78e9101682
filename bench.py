"""Benchmark entry: one JSON line for the driver.

Measures flagship (GPT-2 345M) training throughput on the attached
accelerator — samples/sec/chip, the BASELINE.json headline metric. The
reference publishes no numbers (``"published": {}``), so ``vs_baseline``
reports against this framework's own recorded best (bench_baseline.json, if
present) and 1.0 otherwise.

One process, on the chip or not at all: where jax finds no TPU this exits
non-zero and prints no metric line. A number from a CPU run is a count or a
gate, never a speed, so there is no CPU rerun and no zero-valued record.

Usage: python bench.py [--mesh dp=2,tp=2]
"""

from __future__ import annotations

import argparse
import json
import os
import time


def measure(mesh_key: str = "") -> dict:
    """Run the real train loop on the attached TPU and return the result
    record. ``mesh_key`` ("dp=2,fsdp=2,tp=2") shards the step over that
    factorization instead of pure DP."""
    import jax

    from easydl_tpu.utils.env import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; jax found {device.platform!r} "
            f"({device.device_kind}). No metric printed.")

    import optax

    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.core.mfu import model_flops_per_token, peak_flops_per_chip
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.gpt import SIZES
    from easydl_tpu.models.registry import get_model
    from easydl_tpu.utils.profiling import peak_device_bytes

    n_chips = jax.device_count()
    size, seq_len, steps = "345m", 1024, 15
    grad_accum = 32
    global_batch = 256 * n_chips
    bundle = get_model("gpt", size=size, seq_len=seq_len, remat=True,
                       remat_policy="dots", dtype="bfloat16",
                       fused_loss=False)

    mesh_spec = MeshSpec.parse(mesh_key) if mesh_key else MeshSpec(dp=n_chips)
    if mesh_spec.size != n_chips:
        raise SystemExit(
            f"--mesh {mesh_key} needs {mesh_spec.size} devices, have "
            f"{n_chips}")
    trainer = Trainer(
        init_fn=bundle.init_fn,
        loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(2e-4, weight_decay=0.01),
        config=TrainConfig(global_batch=global_batch, grad_accum=grad_accum),
        mesh_spec=mesh_spec,
    )
    state = trainer.init_state()
    data = iter(bundle.make_data(global_batch))

    # Warmup: compile + 2 steps.
    for _ in range(2):
        state, metrics = trainer.train_step(state, next(data))
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer.train_step(state, next(data))
    # The final metrics depend on the whole step chain (state threads
    # through), so their being ready means every step has run.
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0

    samples_per_sec = steps * global_batch / dt
    per_chip = samples_per_sec / n_chips
    tokens_per_sec = samples_per_sec * seq_len

    # MFU: achieved model FLOP/s over the chip's peak (core/mfu.py: an
    # unknown chip raises; EASYDL_CHIP_PEAK_TFLOPS overrides).
    n_layers, d_model, _ = SIZES[size]
    flops_per_token = model_flops_per_token(
        bundle.param_count_hint, n_layers, d_model, seq_len)
    achieved = tokens_per_sec * flops_per_token / n_chips
    peak = peak_flops_per_chip(device.device_kind)

    baseline_path = os.path.join(os.path.dirname(__file__), "bench_baseline.json")
    vs_baseline = 1.0
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                recorded = json.load(f).get(f"gpt-{size}", 0.0)
            if recorded > 0:
                vs_baseline = per_chip / recorded
        except (OSError, ValueError):
            pass

    return {
        "metric": f"gpt-{size} seq{seq_len} samples/sec/chip "
                  f"({device.platform}, {n_chips} chip)",
        "value": round(per_chip, 3),
        "unit": "samples/sec/chip",
        "vs_baseline": round(vs_baseline, 3),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "step_time_s": round(dt / steps, 4),
        "mfu": round(achieved / peak, 8),
        "model_tflops_per_sec_per_chip": round(achieved / 1e12, 6),
        "peak_tflops_per_chip": round(peak / 1e12, 1),
        "peak_hbm_bytes": peak_device_bytes(),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_chips": n_chips,
        "mesh": mesh_spec.key(),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default="",
                    help="mesh shape key, e.g. dp=2,tp=2 (default: dp=all)")
    args = ap.parse_args()
    print(json.dumps(measure(mesh_key=args.mesh)))


if __name__ == "__main__":
    main()

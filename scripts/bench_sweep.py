"""Perf exploration sweep for the flagship bench config (run on real TPU).

Times several (remat, batch, dtype, attention) variants in one process and
prints a line per config — the evidence base for bench.py's chosen settings.
Usage: python scripts/bench_sweep.py [--steps 20]
"""

from __future__ import annotations

import argparse
import json
import time


def mesh_table(paths) -> None:
    """Aggregate per-shape MFU cells (MULTICHIP_r06-style docs, one
    ``bench.py --mesh KEY`` record per cell) into one table: devices x shape -> MFU /
    samples/s/chip. Multiple docs merge (e.g. a CPU sweep + a later real-
    TPU sweep); later files win on (devices, mesh) collisions."""
    cells = {}
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        for c in doc.get("cells", []):
            cells[(int(c.get("n_chips", 0)), str(c.get("mesh", "")))] = c
    if not cells:
        raise SystemExit("no mesh MFU cells in the given files")
    print(f"{'devices':>7}  {'mesh':24s} {'mfu':>12} "
          f"{'samples/s/chip':>15} {'step_ms':>9}")
    best = {}
    for (n, mesh), c in sorted(cells.items()):
        best.setdefault(n, (0.0, ""))
        if c.get("mfu", 0.0) > best[n][0]:
            best[n] = (c["mfu"], mesh)
        print(f"{n:>7}  {mesh:24s} {c.get('mfu', 0.0):>12.8f} "
              f"{c.get('value', 0.0):>15.3f} "
              f"{1000 * c.get('step_time_s', 0.0):>9.1f}")
    for n, (m, mesh) in sorted(best.items()):
        print(f"BEST {n}dev: {mesh} (mfu {m:.8f})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=15)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--mesh-table", nargs="+", metavar="JSON",
                    help="aggregate MULTICHIP_r06-style docs into one "
                         "per-shape MFU table and exit (no jax import)")
    args = ap.parse_args()
    if args.mesh_table:
        mesh_table(args.mesh_table)
        return

    import jax
    import optax

    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    n_chips = jax.device_count()
    bf16_dots = dict(remat=True, remat_policy="dots", dtype="bfloat16")
    # r2 sweep (kept for the record): f32 b8 27.6 / bf16 b8 37.9 / bf16
    # no-remat and mb>8 OOMed on the f32 logits buffer; b64/a8 39.9,
    # b128/a16 40.1. r3 removes the logits buffer (fused chunked LM loss),
    # so this sweep explores the unlocked microbatch/chunk frontier.
    configs = [
        # (label, model kwargs, per-chip batch, grad_accum)
        ("plain  b64/a8  mb8 (r2 best)",
         dict(fused_loss=False, **bf16_dots), 64, 8),
        ("fused c128 b64/a8  mb8",
         dict(fused_loss=True, loss_chunk=128, **bf16_dots), 64, 8),
        ("fused c128 b128/a16 mb8",
         dict(fused_loss=True, loss_chunk=128, **bf16_dots), 128, 16),
        ("fused c128 b128/a8  mb16",
         dict(fused_loss=True, loss_chunk=128, **bf16_dots), 128, 8),
        ("fused c256 b128/a8  mb16",
         dict(fused_loss=True, loss_chunk=256, **bf16_dots), 128, 8),
        ("fused c512 b128/a8  mb16",
         dict(fused_loss=True, loss_chunk=512, **bf16_dots), 128, 8),
        ("fused c128 b256/a8  mb32",
         dict(fused_loss=True, loss_chunk=128, **bf16_dots), 256, 8),
        ("fused c128 b128/a4  mb32",
         dict(fused_loss=True, loss_chunk=128, **bf16_dots), 128, 4),
        ("fused c128 no-remat b128/a8 mb16",
         dict(fused_loss=True, loss_chunk=128, dtype="bfloat16"), 128, 8),
        # accum_unroll hypothesis: lax.scan unroll lets XLA fuse the
        # accumulation carry update across microbatches. UNMEASURED on
        # TPU so far (the trace numbers once cited for it were retracted:
        # that parser was incoherent).
        ("plain  b256/a32 u1 (r4 bench)",
         dict(fused_loss=False, **bf16_dots), 256, 32, 1),
        ("plain  b256/a32 u2",
         dict(fused_loss=False, **bf16_dots), 256, 32, 2),
        ("plain  b256/a32 u4",
         dict(fused_loss=False, **bf16_dots), 256, 32, 4),
        ("plain  b256/a32 u8",
         dict(fused_loss=False, **bf16_dots), 256, 32, 8),
    ]
    for label, kwargs, per_chip_batch, grad_accum, *rest in configs:
        accum_unroll = rest[0] if rest else 1
        global_batch = per_chip_batch * n_chips
        try:
            bundle = get_model("gpt", size="345m", seq_len=args.seq, **kwargs)
            trainer = Trainer(
                init_fn=bundle.init_fn,
                loss_fn=bundle.loss_fn,
                optimizer=optax.adamw(2e-4, weight_decay=0.01),
                config=TrainConfig(global_batch=global_batch,
                                   grad_accum=grad_accum,
                                   accum_unroll=accum_unroll),
                mesh_spec=MeshSpec(dp=n_chips),
            )
            state = trainer.init_state()
            data = iter(bundle.make_data(global_batch))
            for _ in range(2):
                state, metrics = trainer.train_step(state, next(data))
            float(jax.device_get(metrics["loss"]))
            t0 = time.perf_counter()
            for _ in range(args.steps):
                state, metrics = trainer.train_step(state, next(data))
            float(jax.device_get(metrics["loss"]))
            dt = time.perf_counter() - t0
            sps = args.steps * global_batch / dt / n_chips
            print(f"RESULT {label:28s} {sps:8.2f} samples/s/chip  "
                  f"step {dt / args.steps * 1000:7.1f} ms", flush=True)
            del state, trainer
        except Exception as e:  # OOM etc: report and keep sweeping
            print(f"RESULT {label:28s} FAILED: {type(e).__name__}: {e}",
                  flush=True)


if __name__ == "__main__":
    main()

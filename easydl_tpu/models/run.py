"""``python -m easydl_tpu.models.run`` — the model-zoo entrypoint.

This is the command a job's pods execute (the reference quickstart runs
``python -m model_zoo.iris.dnn_estimator``,
docs/design/elastic-training-operator.md:37; our manifests point here).
Roles:

- ``--role trainer`` (default): single-process training loop with periodic
  checkpointing — the path worker pods run under the elastic runtime too
  (the agent sets the distributed env; see easydl_tpu/elastic/worker.py).
- ``--role evaluator``: checkpoint-following side evaluation
  (easydl_tpu/core/evaluator.py).

Data is synthetic per model bundle, so any config runs hermetically.
"""

from __future__ import annotations

import argparse
import json


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="easydl_tpu model zoo runner")
    ap.add_argument("--model", required=True, help="registry name (mlp, resnet, bert, gpt, granite_hybrid, ouro, deepfm, widedeep)")
    ap.add_argument("--role", choices=["trainer", "evaluator"], default="trainer")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--dp", type=int, default=0, help="data-parallel size (0 = all devices)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (GPipe over the pp mesh axis; "
                         "transformer models only)")
    ap.add_argument("--pp-microbatches", type=int, default=2)
    ap.add_argument("--eval-polls", type=int, default=0, help="evaluator: stop after N evals (0 = forever)")
    ap.add_argument("--model-arg", action="append", default=[],
                    help="k=v forwarded to the model factory (repeatable)")
    ap.add_argument("--profile-dir", default="",
                    help="capture an XLA trace of 3 steady-state steps here")
    ap.add_argument("--data-dir", default="",
                    help="file-backed data: a dir of tokens-*.npy shards "
                         "(LM models) or images.npy/labels.npy "
                         "(classification). Default: the model bundle's "
                         "synthetic stream")
    ap.add_argument("--seq-len", type=int, default=0,
                    help="sequence length for --data-dir token shards "
                         "(default: the model's seq_len model-arg or 128)")
    ap.add_argument("--val-fraction", type=float, default=0.0,
                    help="deterministic held-out fraction of --data-dir "
                         "token windows; trainers read the rest, the "
                         "evaluator reads the holdout")
    return ap


def file_data(args, bundle, seed_offset: int = 0, split: str = "train"):
    """--data-dir -> a dataset matching the model's input contract
    (``data/source.py open_dataset``; --seq-len overrides the model's own)."""
    from easydl_tpu.data import open_dataset

    try:
        return open_dataset(
            args.data_dir, bundle, batch_size=args.batch,
            seq_len=args.seq_len, seed=seed_offset, split=split,
            val_fraction=args.val_fraction)
    except ValueError as e:
        raise SystemExit(f"{e} (--seq-len)") from e


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()

    import jax

    from easydl_tpu.utils.env import configure_compile_cache
    from easydl_tpu.utils.profiling import CompileWatch, peak_device_bytes

    cache_dir = configure_compile_cache()
    compiles = CompileWatch()

    import optax

    from easydl_tpu.core.checkpoint import CheckpointManager
    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.core.metrics import MetricsRecorder
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model
    from easydl_tpu.utils.logging import get_logger

    log = get_logger("models", "run")

    kwargs = {}
    for kv in args.model_arg:
        k, _, v = kv.partition("=")
        try:
            kwargs[k] = json.loads(v)
        except json.JSONDecodeError:
            kwargs[k] = v

    from easydl_tpu.core.mesh import build_mesh
    from easydl_tpu.ops.pipeline import apply_pipeline_config

    pp = max(args.pp, 1)
    n_dev = jax.device_count()
    log.info("device: %s (%s) x%d; compile cache: %s",
             jax.devices()[0].platform, jax.devices()[0].device_kind, n_dev,
             cache_dir or "off")
    if pp > 1 and (n_dev < pp or n_dev % pp):
        # fail here with the cause, not later with an empty/truncated mesh
        ap.error(f"--pp {pp} needs a device count divisible by it "
                 f"(have {n_dev})")
    dp = args.dp or (n_dev // pp)
    mesh = build_mesh(MeshSpec(dp=dp, pp=pp))
    kwargs, rules = apply_pipeline_config(
        args.model, kwargs, mesh, microbatches=args.pp_microbatches)
    bundle = get_model(args.model, **kwargs)

    trainer = Trainer(
        init_fn=bundle.init_fn,
        loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(args.lr),
        config=TrainConfig(global_batch=args.batch, rules=rules),
        mesh=mesh,
    )
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    if args.role == "evaluator":
        if ckpt is None:
            ap.error("--role evaluator requires --ckpt-dir")
        from easydl_tpu.core.evaluator import Evaluator

        if args.data_dir:
            # --val-fraction: a real held-out split; otherwise fall back to
            # a different shuffle order than training (seed_offset=1)
            split = "val" if args.val_fraction else "train"
            eval_data = iter(file_data(args, bundle, seed_offset=1,
                                       split=split))
        else:
            eval_data = iter(bundle.make_data(args.batch, seed=1))
        ev = Evaluator(trainer, ckpt, eval_data, eval_fn=bundle.eval_fn)
        ev.run(poll_interval_s=2.0, max_evals=args.eval_polls or None)
        return

    if ckpt is not None and ckpt.latest_step() is not None:
        # Restore INSTEAD of init, not on top of it: a second full state
        # held through the restore is what overflows a chip the first one
        # already fills.
        state = trainer.restore_from(ckpt)
        log.info("resumed from step %d", state.int_step)
    else:
        state = trainer.init_state()
    first_step = state.int_step + 1
    source = None
    if args.data_dir:
        source = file_data(args, bundle)
        if ckpt is not None and state.int_step > 0:
            # resume the data cursor alongside the model: without this a
            # restored run replays epoch 0 from the start
            from easydl_tpu.data import restore_cursor

            data_state = restore_cursor(source, ckpt, state.int_step)
            if data_state:
                log.info("data cursor resumed: %s", data_state)
        log.info("file-backed data: %s (%d batches/epoch)",
                 args.data_dir, source.batches_per_epoch)
        data = iter(source)
    else:
        data = iter(bundle.make_data(args.batch, seed=0))
    recorder = MetricsRecorder(args.batch, world_size=dp)
    profiler = None
    if args.profile_dir:
        from easydl_tpu.utils.profiling import StepProfiler

        # Window relative to the (possibly resumed) first step, so the
        # recompile-after-restore step is skipped just like a cold start's.
        profiler = StepProfiler(
            args.profile_dir, start_step=state.int_step + 3, num_steps=3
        )
    try:
        while state.int_step < args.steps:
            step = state.int_step
            if profiler is not None:
                profiler.maybe_start(step)
            recorder.start_step()
            state, metrics = trainer.train_step(state, next(data))
            step = state.int_step
            rec = recorder.end_step(step, float(metrics["loss"]))
            if profiler is not None:
                profiler.maybe_stop(step - 1)
            if step % 10 == 0 or step in (first_step, args.steps):
                log.info("step %d loss %.4f (%.1f samples/s)", step, rec.loss,
                         rec.samples_per_sec)
            if step == first_step:
                log.info("first step done: %s", json.dumps(compiles.summary()))
            if ckpt is not None and (step % args.ckpt_every == 0 or step == args.steps):
                ckpt.save(step, state, metadata=(
                    {"data_state": source.state()} if source is not None
                    else None))
            if ckpt is not None:
                # Complete any deferred multi-process commit at the step
                # boundary (collectives on this main thread); no-op otherwise.
                ckpt.finalize()
    finally:
        # Flush an in-flight trace even on a crash — the traced steps are
        # exactly the ones worth inspecting afterwards.
        if profiler is not None:
            profiler.close()
    if ckpt is not None:
        ckpt.wait()
    peak = peak_device_bytes()
    if peak is not None:
        log.info("peak device memory: %d bytes", peak)


if __name__ == "__main__":
    main()

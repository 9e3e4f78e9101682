"""The evaluator pod's entrypoint — the reference's third pod role, live
under the operator (docs/design/elastic-training-operator.md:43-44,79-85:
side evaluation alongside training, replicas 1).

Launched by the operator when the JobResource carries an ``evaluator`` role
(Brain adds one whenever the ElasticJob defines the role). Like the worker
pods it derives everything from the shared workdir: waits for the trainer's
``job.json``, builds the same model bundle, then follows the training run's
checkpoint directory with :class:`~easydl_tpu.core.evaluator.Evaluator` —
never joining the training collective, so worker membership can change or
crash freely without touching evaluation.

Each evaluated checkpoint appends one JSON line to ``<workdir>/eval.jsonl``
(override with ``--out``). Exit: when the job's DONE marker exists and the
final committed checkpoint has been evaluated, the process exits 0 — the
pod ends Succeeded on its own rather than waiting for the operator's
terminal GC to kill it.

``python -m easydl_tpu.elastic.evaluator_main --workdir <shared dir>``
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser(description="easydl_tpu evaluator pod")
    ap.add_argument("--workdir", required=True, help="shared job workdir")
    ap.add_argument("--poll-interval", type=float, default=1.0)
    ap.add_argument("--batches-per-eval", type=int, default=4)
    ap.add_argument("--out", default="",
                    help="eval metrics JSONL (default <workdir>/eval.jsonl)")
    ap.add_argument("--config-timeout", type=float, default=300.0,
                    help="max wait for the trainer to write job.json")
    args = ap.parse_args()

    workdir = args.workdir
    out_path = args.out or os.path.join(workdir, "eval.jsonl")
    cfg_path = os.path.join(workdir, "job.json")
    done_path = os.path.join(workdir, "DONE")

    # The operator may start this pod before the trainer has written the
    # worker config (pods launch in parallel off the same JobResource).
    deadline = time.monotonic() + args.config_timeout
    while not os.path.exists(cfg_path):
        if time.monotonic() > deadline:
            raise SystemExit(f"no {cfg_path} after {args.config_timeout}s — "
                             "is the trainer pod running?")
        time.sleep(0.5)
    with open(cfg_path) as f:
        cfg = json.load(f)

    model_kwargs = dict(cfg.get("model_kwargs", {}))
    if model_kwargs.get("embedding") == "ps":
        # The PS-backed sparse tower lives on the PS tier; a side evaluator
        # would need its own PS read path. Not supported yet — fail loudly
        # instead of evaluating a model with missing parameters.
        raise SystemExit("evaluator does not support embedding='ps' jobs")

    import jax
    import optax

    from easydl_tpu.core import MeshSpec, Trainer, TrainConfig, build_mesh
    from easydl_tpu.core.checkpoint import CheckpointManager
    from easydl_tpu.core.evaluator import Evaluator
    from easydl_tpu.models import get_model
    from easydl_tpu.utils.logging import get_logger

    log = get_logger("elastic", "evaluator")

    bundle = get_model(cfg["model"], **model_kwargs)
    global_batch = int(cfg.get("global_batch", 32))
    # The evaluator's own (usually single-host) mesh: reshard-on-restore
    # absorbs any mismatch with the training mesh.
    trainer = Trainer(
        init_fn=bundle.init_fn,
        loss_fn=bundle.loss_fn,
        optimizer=optax.adam(float(cfg.get("lr", 1e-3))),
        config=TrainConfig(global_batch=global_batch,
                           seed=int(cfg.get("seed", 0))),
        mesh=build_mesh(MeshSpec(dp=jax.device_count())),
    )
    ckpt = CheckpointManager(os.path.join(workdir, "ckpt"), async_save=False)

    val_fraction = float(cfg.get("val_fraction", 0.0))
    if cfg.get("data_dir"):
        from easydl_tpu.data import open_dataset

        # a real holdout when the job carved one; otherwise a different
        # shuffle order than training (seed 1)
        data = iter(open_dataset(
            cfg["data_dir"], bundle, batch_size=global_batch,
            seq_len=int(cfg.get("seq_len", 0)), seed=1,
            split="val" if val_fraction else "train",
            val_fraction=val_fraction))
    else:
        data = iter(bundle.make_data(global_batch, seed=1))

    def append_result(result) -> None:
        with open(out_path, "a") as f:
            f.write(json.dumps(result) + "\n")

    ev = Evaluator(trainer, ckpt, data, eval_fn=bundle.eval_fn,
                   batches_per_eval=args.batches_per_eval,
                   on_result=append_result)
    log.info("following %s/ckpt (results -> %s)", workdir, out_path)
    while True:
        # DONE is checked BEFORE polling: it is written only after the final
        # save commits, so "DONE was already visible AND the poll found
        # nothing new" proves the final checkpoint is evaluated. (Checking
        # after could race a commit that lands between poll and check,
        # skipping the last eval.)
        done_before = os.path.exists(done_path)
        evaluated = ev.poll_once()
        if evaluated is None:
            if done_before:
                log.info("job done; %d checkpoints evaluated",
                         len(ev.results))
                return
            time.sleep(args.poll_interval)


if __name__ == "__main__":
    main()

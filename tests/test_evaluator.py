"""Evaluator-role tests: checkpoint-following side evaluation, including
restore onto a different mesh than training saved (SURVEY.md §2 evaluator
row; docs/design/elastic-training-operator.md:43-44,79-85)."""

import jax.numpy as jnp
import optax
import pytest

from easydl_tpu.core.checkpoint import CheckpointManager
from easydl_tpu.core.evaluator import Evaluator
from easydl_tpu.core.mesh import MeshSpec
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models.registry import get_model


def make_trainer(bundle, spec, batch=16):
    return Trainer(
        init_fn=bundle.init_fn,
        loss_fn=bundle.loss_fn,
        optimizer=optax.adam(1e-2),
        config=TrainConfig(global_batch=batch, compute_dtype=jnp.float32),
        mesh_spec=spec,
    )


@pytest.fixture(scope="module")
def mlp_bundle():
    return get_model("mlp", features=(32, 32))


def test_evaluator_follows_checkpoints(tmp_path, eight_devices, mlp_bundle):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    trainer = make_trainer(mlp_bundle, MeshSpec(dp=4))
    state = trainer.init_state()
    data = iter(mlp_bundle.make_data(16, seed=0))

    # evaluator on a DIFFERENT mesh (dp=2) — reshard-on-restore
    ev_trainer = make_trainer(mlp_bundle, MeshSpec(dp=2))
    ev = Evaluator(
        ev_trainer, mgr, iter(mlp_bundle.make_data(16, seed=7)),
        eval_fn=mlp_bundle.eval_fn, batches_per_eval=2,
    )
    assert ev.poll_once() is None  # nothing saved yet

    for _ in range(3):
        state, _ = trainer.train_step(state, next(data))
    mgr.save(3, state)
    r1 = ev.poll_once()
    assert r1 is not None and r1["step"] == 3 and "accuracy" in r1
    assert ev.poll_once() is None  # same step: not re-evaluated

    for _ in range(3):
        state, _ = trainer.train_step(state, next(data))
    mgr.save(6, state)
    r2 = ev.poll_once()
    assert r2 is not None and r2["step"] == 6
    assert len(ev.results) == 2


def test_evaluator_run_loop_stops(tmp_path, eight_devices, mlp_bundle):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    trainer = make_trainer(mlp_bundle, MeshSpec(dp=1))
    state = trainer.init_state()
    mgr.save(1, state)
    ev = Evaluator(
        trainer, mgr, iter(mlp_bundle.make_data(16, seed=3)), batches_per_eval=1
    )
    ev.run(poll_interval_s=0.01, max_evals=1)  # returns after one eval
    assert [r["step"] for r in ev.results] == [1.0]


def test_model_zoo_runner_cli(tmp_path):
    """The manifests' entry command works end-to-end: train with
    checkpoints, then side-evaluate the saved steps."""
    import subprocess
    import sys

    env_cmd = [sys.executable, "-m", "easydl_tpu.models.run"]
    ck = str(tmp_path / "ck")
    r = subprocess.run(
        env_cmd + ["--model", "mlp", "--steps", "6", "--batch", "8",
                   "--ckpt-dir", ck, "--ckpt-every", "3",
                   "--model-arg", "features=[16,16]"],
        capture_output=True, text=True, timeout=300,
        env=_cpu_env(),
    )
    assert r.returncode == 0, r.stderr
    r = subprocess.run(
        env_cmd + ["--model", "mlp", "--role", "evaluator", "--ckpt-dir", ck,
                   "--eval-polls", "1", "--batch", "8",
                   "--model-arg", "features=[16,16]"],
        capture_output=True, text=True, timeout=300,
        env=_cpu_env(),
    )
    assert r.returncode == 0, r.stderr
    assert "eval @ step" in r.stderr


def _cpu_env():
    # the canonical forced-CPU recipe
    from easydl_tpu.utils.env import cpu_subprocess_env

    return cpu_subprocess_env(8)


def test_profiling_trace_capture(tmp_path):
    """--profile-dir captures an XLA trace of steady-state steps."""
    import glob
    import subprocess
    import sys

    prof = str(tmp_path / "prof")
    r = subprocess.run(
        [sys.executable, "-m", "easydl_tpu.models.run", "--model", "mlp",
         "--steps", "8", "--batch", "8", "--model-arg", "features=[16,16]",
         "--profile-dir", prof],
        capture_output=True, text=True, timeout=300, env=_cpu_env(),
    )
    assert r.returncode == 0, r.stderr
    traces = glob.glob(prof + "/**/*.trace.json.gz", recursive=True) + \
        glob.glob(prof + "/**/*.xplane.pb", recursive=True)
    assert traces, f"no trace files under {prof}: {r.stderr[-500:]}"


def test_evaluator_main_pod_entrypoint(tmp_path, eight_devices):
    """The evaluator POD path (easydl_tpu/elastic/evaluator_main.py): given
    a workdir the trainer/workers populated (job.json, ckpt/, DONE), the
    subprocess evaluates the latest checkpoint, appends eval.jsonl, and
    exits 0 on its own (the lifecycle test covers it under the operator)."""
    import json
    import os
    import subprocess
    import sys

    workdir = tmp_path / "work"
    workdir.mkdir()
    cfg = {"model": "mlp", "model_kwargs": {"features": [32, 32]},
           "global_batch": 16, "lr": 1e-2, "seed": 0}
    (workdir / "job.json").write_text(json.dumps(cfg))

    bundle = get_model("mlp", features=(32, 32))
    trainer = Trainer(
        init_fn=bundle.init_fn,
        loss_fn=bundle.loss_fn,
        optimizer=optax.adam(1e-2),
        config=TrainConfig(global_batch=16),
        mesh_spec=MeshSpec(dp=8),
    )
    state = trainer.init_state()
    batch = next(iter(bundle.make_data(16, seed=0)))
    for _ in range(2):
        state, _ = trainer.train_step(state, batch)
    mgr = CheckpointManager(str(workdir / "ckpt"), async_save=False)
    mgr.save(2, state)
    (workdir / "DONE").write_text("2")

    res = subprocess.run(
        [sys.executable, "-m", "easydl_tpu.elastic.evaluator_main",
         "--workdir", str(workdir), "--batches-per-eval", "2",
         "--poll-interval", "0.2"],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    lines = (workdir / "eval.jsonl").read_text().strip().splitlines()
    evals = [json.loads(ln) for ln in lines]
    assert len(evals) == 1
    assert evals[0]["step"] == 2.0
    assert "loss" in evals[0] and evals[0]["loss"] == evals[0]["loss"]

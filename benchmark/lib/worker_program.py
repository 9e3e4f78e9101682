"""The elastic worker's programs, built in a process of the benchmark's own:
the ``Trainer`` the worker builds for a job (``elastic/worker.py``: the
registry's model, ``optax.adam(lr)``, the job's batch and seed) under a mesh
the benchmark names, its step program compiled ahead, and a committed
checkpoint restored through it.

A chip belongs to one process, so each function here runs where no worker
lives: after the job has ended.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple


def trainer_for(config: Dict[str, Any], worker_config: Dict[str, Any],
                devices: List[Any], mesh: Optional[str] = None) -> Any:
    import optax

    from lib import program

    return program.build_trainer(
        config, worker_config["global_batch"], worker_config["grad_accum"],
        optax.adam(worker_config["lr"]), worker_config["seed"], devices,
        mesh)[1]


def device_and_step_memory(config: Dict[str, Any], chips: int,
                           worker_config: Dict[str, Any],
                           mesh: Optional[str] = None
                           ) -> Tuple[Dict[str, Any], Dict[str, int]]:
    """The device as jax reports it, and the bytes per device the worker's
    step program needs under ``mesh`` (the configuration's own where None):
    the same ``Trainer`` the worker builds, compiled ahead — from the cache
    where a run has filled it, into the cache where none has."""
    import jax
    import jax.numpy as jnp

    from easydl_tpu.utils.env import configure_compile_cache

    from lib import devices as dev, hlo

    configure_compile_cache()
    devices = dev.require(config["platform"], chips)
    trainer = trainer_for(config, worker_config, devices, mesh)
    tokens = jax.ShapeDtypeStruct(
        (worker_config["global_batch"],
         worker_config["model_kwargs"]["seq_len"]), jnp.int32)
    compiled = trainer.step_fn.lower(
        trainer.abstract_state(),
        {"inputs": tokens, "targets": tokens}).compile()
    return dev.describe(devices), hlo.step_memory(compiled)


def restores_that_differ(config: Dict[str, Any], chips: int,
                         worker_config: Dict[str, Any], ckpt_dir: str,
                         step: int, meshes: List[str]
                         ) -> Tuple[List[str], int]:
    """Checkpoint ``step`` restored under each of ``meshes`` by the program's
    own ``Trainer.restore_from``, and every leaf of each restore (step,
    parameters, optimizer state, key), gathered to the host, held bit for
    bit to the array the checkpoint's FILES hold under the leaf's path, read
    plainly (``lib/checkpoint_files``: numpy, nothing of the program): a
    restore, under the mesh that saved or under another, moves bytes and
    computes nothing. Returns the leaves that differ from the files, or that
    one side lacks, each with its mesh, and how many leaves the files hold.

    This is the program's restore in a process of the benchmark's own, after
    the window: not the worker's timed restore, which the replayed losses
    hold."""
    import jax
    import numpy as np

    from easydl_tpu.core.checkpoint import CheckpointManager

    from lib import checkpoint_files, devices as dev

    devices = dev.require(config["platform"], chips)
    files = checkpoint_files.read(ckpt_dir, step)
    ckpt = CheckpointManager(ckpt_dir, async_save=False)
    differ: List[str] = []
    for mesh in meshes:
        state = trainer_for(config, worker_config, devices,
                            mesh).restore_from(ckpt, step)
        restored = set()
        for path, leaf in jax.tree_util.tree_leaves_with_path(state):
            key = jax.tree_util.keystr(path)
            restored.add(key)
            want = files.get(key)
            if want is None or not checkpoint_files.same_bits(
                    np.asarray(leaf), want):
                differ.append(f"{mesh}:{key}")
        differ += [f"{mesh}:{key} (in the files, not restored)"
                   for key in files if key not in restored]
        del state
    return differ, len(files)

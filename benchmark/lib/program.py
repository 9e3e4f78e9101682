"""The system under test, built the way its own entry points build it."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple


def build_trainer(config: Dict[str, Any], global_batch: int, grad_accum: int,
                  optimizer: Any, seed: int, devices: List[Any],
                  mesh: Optional[str] = None) -> Tuple[Any, Any]:
    """``(bundle, trainer)`` for a configuration file: the model bundle from
    the program's registry (``factory``, ``kwargs``) and a ``Trainer`` on the
    file's ``mesh`` over ``devices`` — what ``models/run.py`` and the elastic
    worker construct. ``mesh`` names another shape for the same devices: a
    mix whose job changes its mesh states each in its own file."""
    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    bundle = get_model(config["factory"], **config["kwargs"])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn, optimizer=optimizer,
        config=TrainConfig(global_batch=global_batch, grad_accum=grad_accum,
                           seed=seed),
        mesh=build_mesh(MeshSpec.parse(mesh or config["mesh"]),
                        devices=devices))
    return bundle, trainer

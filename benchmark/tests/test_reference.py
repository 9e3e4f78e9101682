"""reference_gpt2 against the program, in process, on the CPU at the test
size: in float32 the two are the same mathematics and agree to rounding;
in bf16 the comparison's errors sit where the configuration file's
tolerances expect them, and a lower precision than stated fails."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import optax
import pytest

from conftest import BENCH
from lib import check_gpt2


def _check(dtype, compute_dtype, tolerances=None, seed=0):
    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    with open(os.path.join(BENCH, "configs", "gpt2-test.json")) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config["kwargs"]["dtype"] = dtype
    if tolerances:
        config["check"]["tolerances"] = tolerances
    bundle = get_model(config["factory"], **config["kwargs"])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=2, compute_dtype=compute_dtype,
                           seed=seed),
        mesh=build_mesh(MeshSpec.parse("dp=1"), devices=jax.devices()[:1]))
    return check_gpt2.check(config, bundle, trainer, seed)


def test_float32_program_equals_the_reference_to_rounding():
    tight = {"loss_abs": 1e-5, "hidden_rel_rms": 1e-5,
             "grad_rel_rms_worst": 1e-4}
    result = _check("float32", jnp.float32, tight)
    assert result["ok"], result


@pytest.mark.parametrize("seed", [0, 7])
def test_bf16_program_sits_inside_the_files_tolerances(seed):
    result = _check("bfloat16", jnp.bfloat16, seed=seed)
    assert result["ok"], result
    # and not by a mile: bf16 is visible, so a tolerance twice this error
    # still tells bf16 from float32
    assert result["errors"]["hidden_rel_rms"] > 1e-3


def test_a_lower_precision_than_stated_fails():
    """The tolerances that float32 meets fail the bf16 program: a silent
    drop of precision does not pass as the same configuration."""
    tight = {"loss_abs": 1e-5, "hidden_rel_rms": 1e-5,
             "grad_rel_rms_worst": 1e-4}
    result = _check("bfloat16", jnp.bfloat16, tight)
    assert not result["ok"]

"""reference_ouro against the program, in process, on the CPU at the test
size: in float32 the two are the same mathematics and agree to rounding; in
bf16 the comparison's errors sit where the configuration file's tolerances
expect them, and a lower precision than stated fails. The reference imports
nothing from the program."""

import ast
import copy
import json
import os

import jax
import jax.numpy as jnp
import optax
import pytest

from conftest import BENCH
from lib import check_ouro


def _check(dtype, compute_dtype, tolerances=None, seed=0):
    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    with open(os.path.join(BENCH, "configs", "ouro-test.json")) as f:
        config = copy.deepcopy(json.load(f))
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
    return check_ouro.check(config, bundle, trainer, seed)


TIGHT = dict({f"state_rel_rms_pass_{t}": 5e-5 for t in range(4)},
             rope_table_abs=1e-6, exit_given_gate_abs=1e-6,
             loss_abs=5e-5, exit_abs=5e-5, grad_rel_rms_worst=1e-3)


@pytest.mark.parametrize("seed", [0, 7])
def test_float32_program_equals_the_reference_to_rounding(seed):
    result = _check("float32", jnp.float32, TIGHT, seed=seed)
    assert result["ok"], result
    assert result["errors"]["grad_rel_rms_all"] > 0  # it did compare


@pytest.mark.parametrize("seed", [0, 7, 2147483653])
def test_bf16_program_sits_inside_the_files_tolerances(seed):
    result = _check("bfloat16", jnp.bfloat16, seed=seed)
    assert result["ok"], result
    # and not by a mile: bf16 is visible in every pass
    for t in range(4):
        assert result["errors"][f"state_rel_rms_pass_{t}"] > 1e-3


def test_a_lower_precision_than_stated_fails():
    result = _check("bfloat16", jnp.bfloat16, TIGHT)
    assert not result["ok"]


def test_the_gradients_go_through_the_head_the_whole_sequences_get(
        monkeypatch):
    """The rule is asked at the whole sequences' shape and its answer handed
    to the prefix: with the constant lowered the check's gradients run the
    fused weighted head and still agree to rounding."""
    from easydl_tpu.models import lm

    monkeypatch.setattr(lm, "FUSED_HEAD_LOGITS_BYTES", 0)
    result = _check("float32", jnp.float32, TIGHT)
    assert result["ok"], result
    assert result["errors"]["grad_head_fused"] is True


def test_the_gradient_assembled_by_pass_is_jax_grad_of_the_whole_loss():
    """``loss_and_grads_by_pass`` (what the check runs: one compiled pass,
    the chain rule over the passes by hand) against ``loss_and_grads``
    (``jax.grad`` of the whole loss), float32, every leaf."""
    import numpy as np

    from easydl_tpu.core.sharding import unbox
    from easydl_tpu.models.registry import get_model
    from lib import reference_ouro as ref

    bundle = get_model("ouro", size="test", seq_len=32, vocab=256)
    params = unbox(bundle.init_fn(jax.random.PRNGKey(3)))
    plain = check_ouro.to_reference(
        dict(params, exit_gate=params["exit_gate"] * 20.0))
    tokens = np.random.default_rng(3).integers(0, 256, (2, 33), np.int32)
    hp = {"eps": 1e-6, "rope_theta": 1e6, "total_ut_steps": 4, "beta": 0.05}
    whole = ref.loss_and_grads(plain, tokens[:, :-1], tokens[:, 1:], hp)
    by_pass = ref.loss_and_grads_by_pass(plain, tokens[:, :-1],
                                         tokens[:, 1:], hp)
    assert float(by_pass[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    a, b = jax.tree.leaves(by_pass[1]), jax.tree.leaves(whole[1])
    assert len(a) == len(b) == 5 + 3 * 11
    for x, y in zip(a, b):
        assert x.shape == y.shape
        assert float(jnp.linalg.norm(x - y)) \
            <= 1e-5 * float(jnp.linalg.norm(y)) + 1e-9


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "lib", "reference_ouro.py")) as f:
        source = f.read()
    tree = ast.parse(source)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "functools", "math", "typing", "jax"}
    assert "easydl_tpu" not in source
    assert 'default_matmul_precision("highest")' in source

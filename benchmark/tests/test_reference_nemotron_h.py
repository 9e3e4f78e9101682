"""reference_nemotron_h against the program, in process, on the CPU at the test
size: in float32 the two are the same mathematics and agree to rounding; in
bf16 the comparison's errors sit where the configuration file's tolerances
expect them; and each fault the tolerances are there for — bf16 router
logits, a selection that is not the largest of scores plus bias, a gated norm
over all channels at once, a head that reads another group's B and C, a gated
SwiGLU-less expert that forgets the square — fails at least one of them. The
reference imports nothing from the program."""

import ast
import copy
import json
import os

import jax
import jax.numpy as jnp
import optax
import pytest

from conftest import BENCH
from lib import check_nemotron_h
from lib import reference_nemotron_h as ref


def _config():
    with open(os.path.join(BENCH, "configs", "nemotron-test.json")) as f:
        return copy.deepcopy(json.load(f))


def _check(dtype, compute_dtype, tolerances=None, seed=0):
    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    config = _config()
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
    return check_nemotron_h.check(config, bundle, trainer, seed)


TIGHT = dict({f"state_rel_rms_block_{b}": 2e-5 for b in range(4)},
             state_rel_rms_final=2e-5, token_rel_max=1e-4,
             router_logits_rel=1e-5, ssm_conv_token_rel_max=2e-5,
             ssd_token_rel_max=4e-5, gated_norm_token_rel_max=2e-5,
             moe_dropped=0, chosen_sets_differ_share=0.0,
             chosen_not_top6_share=0.0, loss_abs=5e-5,
             grad_rel_rms_worst=1e-3)


def _failing(result):
    return {k for k, tol in result["tolerances"].items()
            if not result["errors"][k] <= tol}


@pytest.mark.parametrize("seed", [0, 7])
def test_float32_program_equals_the_reference_to_rounding(seed):
    result = _check("float32", jnp.float32, TIGHT, seed=seed)
    assert result["ok"], result
    assert result["errors"]["grad_rel_rms_all"] > 0  # it did compare
    assert result["errors"]["grad_leaves"] == 3 * 9 + 5 + 3 * 7 + 3
    assert 1.0 < result["counters"]["moe_rows_per_token"] < 2.0  # 3 x 8 / 16


@pytest.mark.parametrize("seed", [0, 2147483653])
def test_bf16_program_sits_inside_the_files_tolerances(seed):
    result = _check("bfloat16", jnp.bfloat16, seed=seed)
    assert result["ok"], result
    # and not by a mile: bf16 is visible in every block and in the mixer
    for b in range(4):
        assert result["errors"][f"state_rel_rms_block_{b}"] > 1e-3
    assert result["errors"]["ssm_conv_token_rel_max"] > 1e-3
    # the router's float32 arithmetic is not where bf16 shows
    assert result["errors"]["router_logits_rel"] <= 1e-5


def test_a_lower_precision_than_stated_fails():
    assert not _check("bfloat16", jnp.bfloat16, TIGHT)["ok"]


def test_bf16_router_logits_fail(monkeypatch):
    from easydl_tpu.ops import moe

    real = moe.route

    def rounded(h, kernel, k, scaling, bias=None):
        logits, _, _ = real(h, kernel, k, scaling, bias)
        # an explicit rounding: a convert pair may be optimised away
        logits = jax.lax.reduce_precision(logits, 8, 7)
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + bias, k)
        top = jnp.take_along_axis(scores, chosen, -1)
        return logits, chosen, scaling * top / jnp.sum(top, -1, keepdims=True)

    monkeypatch.setattr(moe, "route", rounded)
    assert "router_logits_rel" in _failing(_check("bfloat16", jnp.bfloat16))


def test_a_selection_that_is_not_the_largest_fails_alone(monkeypatch):
    """A selection on other scores than the logits' own plus the bias passes
    every limit on the states — the reference's sub-layers take the
    program's sets — and not the one that holds the sets to the program's own
    logits."""
    from easydl_tpu.ops import moe

    real = moe.route

    def tipped(h, kernel, k, scaling, bias=None):
        logits, _, _ = real(h, kernel, k, scaling, bias)
        scores = jax.nn.sigmoid(logits)
        tip = 1e-3 * (jnp.arange(scores.shape[-1]) % 2)
        _, chosen = jax.lax.top_k(scores + bias + tip, k)
        top = jnp.take_along_axis(scores, chosen, -1)
        return logits, chosen, scaling * top / jnp.sum(top, -1, keepdims=True)

    monkeypatch.setattr(moe, "route", tipped)
    result = _check("bfloat16", jnp.bfloat16)
    assert _failing(result) == {"chosen_not_top6_share"}, result["errors"]


def test_a_gated_norm_over_all_channels_fails(monkeypatch):
    """GraniteMoeHybrid's norm (one mean square over the 64 inner channels)
    under NemotronH's name: the norm on equal inputs shows it."""
    from easydl_tpu.models import transformer

    real = transformer.gated_rmsnorm
    monkeypatch.setattr(
        transformer, "gated_rmsnorm",
        lambda y, z, weight, eps, groups=1: real(y, z, weight, eps, 1))
    failing = _failing(_check("bfloat16", jnp.bfloat16))
    assert "gated_norm_token_rel_max" in failing
    assert not {"ssd_token_rel_max", "ssm_conv_token_rel_max",
                "router_logits_rel"} & failing


def test_a_head_that_reads_another_groups_b_and_c_fails(monkeypatch):
    """Held to float32's limits: at this size's seeded weights (maps of a
    64-wide state are hundredths) the skip ``D x`` is nearly all of the
    scan's result and the state's part a hundredth of it; at the published
    width the maps are of order one and the chip's reading is whole (the
    configuration file's ``measured``)."""
    from easydl_tpu.models import transformer

    real = transformer.ssd_scan
    monkeypatch.setattr(
        transformer, "ssd_scan",
        lambda x, dt, A, B, C, D, *, chunk: real(
            x, dt, A, jnp.roll(B, 1, 2), jnp.roll(C, 1, 2), D, chunk=chunk))
    failing = _failing(_check("float32", jnp.float32, TIGHT))
    assert "ssd_token_rel_max" in failing
    assert not {"gated_norm_token_rel_max", "ssm_conv_token_rel_max",
                "router_logits_rel"} & failing


def test_an_expert_that_forgets_the_square_fails(monkeypatch):
    """``relu(h W_up) W_down`` for ``relu(h W_up)^2 W_down``: the states and
    the gradients show it (at seeded weights the up products are hundredths,
    so the square is most of the value)."""
    from easydl_tpu.ops import moe

    monkeypatch.setattr(moe, "_activation", lambda *pre: jax.nn.relu(pre[0]))
    result = _check("float32", jnp.float32, TIGHT)
    assert {"state_rel_rms_block_0", "grad_rel_rms_worst"} \
        <= _failing(result), result["errors"]


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "lib", "reference_nemotron_h.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "functools", "math", "typing", "jax"}


def test_the_recurrence_reads_b_and_c_by_group_and_the_norm_by_group():
    """The reference's own two mechanisms by hand at a tiny size: head ``h``
    reads group ``h // (H / G)``; the gated norm's statistics are a group's
    alone."""
    import numpy as np

    r = np.random.default_rng(0)
    b, s, H, P, G, N = 1, 5, 4, 3, 2, 2
    x = r.normal(size=(b, s, H, P)).astype(np.float32)
    dt = r.uniform(0.1, 0.5, size=(b, s, H)).astype(np.float32)
    A = -r.uniform(0.5, 1.5, size=H).astype(np.float32)
    B = r.normal(size=(b, s, G, N)).astype(np.float32)
    C = r.normal(size=(b, s, G, N)).astype(np.float32)
    D = r.normal(size=H).astype(np.float32)
    want = np.zeros_like(x)
    for h in range(H):
        g = h // (H // G)
        state = np.zeros((P, N), np.float32)
        for t in range(s):
            state = np.exp(dt[0, t, h] * A[h]) * state + dt[0, t, h] \
                * np.outer(x[0, t, h], B[0, t, g])
            want[0, t, h] = state @ C[0, t, g] + D[h] * x[0, t, h]
    got = ref.recurrence(*(jnp.asarray(a) for a in (x, dt, A, B, C, D)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)

    y = r.normal(size=(1, 2, 8)).astype(np.float32)
    z = r.normal(size=(1, 2, 8)).astype(np.float32)
    gain = r.normal(size=8).astype(np.float32)
    gated = (y * z / (1 + np.exp(-z))).reshape(1, 2, 2, 4)
    normed = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.grouped_gated_norm(jnp.asarray(y), jnp.asarray(z),
                                          jnp.asarray(gain), 2, 1e-5)),
        normed.reshape(1, 2, 8) * gain, rtol=1e-5, atol=1e-6)

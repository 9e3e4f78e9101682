"""reference_zaya against the program, in process, on the CPU at the test
size: in float32 the two are the same mathematics and agree to rounding; in
bf16 the comparison's errors sit where the configuration file's tolerances
expect them; and each fault the tolerances are there for — bf16 router
logits, a choice that is not the largest, a shift that wraps round instead of
padding or runs the wrong way, a value head taken from the current token —
fails at least one of them. The reference imports nothing from the program,
and its pieces give the gradient its one function gives."""

import ast
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from conftest import BENCH
from lib import check_zaya
from lib import reference_zaya as ref


def _config():
    with open(os.path.join(BENCH, "configs", "zaya1-test.json")) as f:
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
    return check_zaya.check(config, bundle, trainer, seed)


TIGHT = dict({f"state_rel_rms_layer_{l}": 2e-5 for l in range(4)},
             state_rel_rms_final=2e-5, router_state_rel_rms=2e-5,
             token_rel_max=1e-4, router_logits_rel=1e-5,
             cca_mix_token_rel_max=2e-5, moe_dropped=0,
             chosen_differ_share=0.0, chosen_not_top1_share=0.0,
             loss_abs=5e-5, grad_rel_rms_worst=1e-3)


def _failing(result):
    return {k for k, tol in result["tolerances"].items()
            if not result["errors"][k] <= tol}


@pytest.mark.parametrize("seed", [0, 7])
def test_float32_program_equals_the_reference_to_rounding(seed):
    result = _check("float32", jnp.float32, TIGHT, seed=seed)
    assert result["ok"], result
    assert result["errors"]["grad_rel_rms_all"] > 0  # it did compare
    assert 0.2 < result["counters"]["moe_rows_per_token"] < 0.8  # 8 / 17
    assert result["counters"]["moe_skipped"] > 0


@pytest.mark.parametrize("seed", [0, 7, 2147483653])
def test_bf16_program_sits_inside_the_files_tolerances(seed):
    result = _check("bfloat16", jnp.bfloat16, seed=seed)
    assert result["ok"], result
    # and not by a mile: bf16 is visible in every layer and in the mix
    for l in range(4):
        assert result["errors"][f"state_rel_rms_layer_{l}"] > 1e-3
    assert result["errors"]["cca_mix_token_rel_max"] > 1e-3
    # the router's float32 arithmetic is not where bf16 shows
    assert result["errors"]["router_logits_rel"] <= 1e-5


def test_a_lower_precision_than_stated_fails():
    assert not _check("bfloat16", jnp.bfloat16, TIGHT)["ok"]


def test_bf16_router_logits_fail(monkeypatch):
    from easydl_tpu.ops import moe

    real = moe.route_mlp

    def rounded(h, state, w, eps):
        r, logits, _, _ = real(h, state, w, eps)
        # an explicit rounding: a convert pair may be optimised away
        logits = jax.lax.reduce_precision(logits, 8, 7)
        p = jax.nn.softmax(logits, -1)
        chosen = jnp.argmax(p, -1).astype(jnp.int32)[:, None]
        return r, logits, chosen, jnp.take_along_axis(p, chosen, -1)

    monkeypatch.setattr(moe, "route_mlp", rounded)
    assert "router_logits_rel" in _failing(_check("bfloat16", jnp.bfloat16))


def test_a_choice_that_is_not_the_largest_fails_alone(monkeypatch):
    """A choice on other probabilities than the logits' own (a bias that
    tips near-ties) passes every limit on the states — the reference's
    layers take the program's choices — and not the one that holds the
    choice to the program's own logits."""
    from easydl_tpu.ops import moe

    real = moe.route_mlp

    def tipped(h, state, w, eps):
        r, logits, _, _ = real(h, state, w, eps)
        p = jax.nn.softmax(logits, -1)
        bias = 1e-5 * (jnp.arange(p.shape[-1]) % 2)
        chosen = jnp.argmax(p + bias, -1).astype(jnp.int32)[:, None]
        return r, logits, chosen, jnp.take_along_axis(p, chosen, -1)

    monkeypatch.setattr(moe, "route_mlp", tipped)
    result = _check("bfloat16", jnp.bfloat16)
    assert _failing(result) == {"chosen_not_top1_share"}, result["errors"]


@pytest.mark.parametrize("fault", ["wraps", "wrong_way", "no_value_shift"])
def test_a_wrong_shift_fails_the_mix_on_equal_inputs(monkeypatch, fault):
    """The readings the limit on ``cca_mix_token_rel_max`` stands between:
    a shift that rolls the last position round to the first is wrong at
    position 0 alone; one that reads the NEXT token is wrong everywhere (and
    no longer causal); a second value head from the current token likewise."""
    from easydl_tpu.models import transformer

    real = transformer._shift

    def wraps(x, by=1):
        return jnp.roll(x, by, axis=1)

    def wrong_way(x, by=1):
        return jnp.flip(real(jnp.flip(x, 1), by), 1)

    monkeypatch.setattr(transformer, "_shift", {
        "wraps": wraps, "wrong_way": wrong_way,
        "no_value_shift": lambda x, by=1: x}[fault])
    result = _check("bfloat16", jnp.bfloat16)
    assert "cca_mix_token_rel_max" in _failing(result)
    assert result["errors"]["cca_mix_token_rel_max"] > 0.3, result["errors"]


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "lib", "reference_zaya.py")) as f:
        tree = ast.parse(f.read())
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            modules.add((node.module or "").split(".")[0])
    assert modules <= {"__future__", "functools", "math", "typing", "jax"}


def _seeded_reference(seed=0, layers=2, held=(0, 8)):
    """Seeded parameters in the reference's own layout at a tiny size."""
    d, heads, groups, hd, r, f, vocab = 32, 4, 2, 8, 8, 16, 64
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64 * layers + 8))

    def normal(*shape, scale=0.1):
        return scale * jax.random.normal(next(keys), shape)

    def layer():
        return {
            "n1": 1 + normal(d), "n2": 1 + normal(d),
            "wq": normal(d, heads, hd), "wk": normal(d, groups, hd),
            "wv": normal(d, groups, hd), "wo": normal(heads, hd, d),
            "conv0": normal(2, heads + groups, hd, scale=0.5),
            "conv0_b": normal(heads + groups, hd),
            "conv1": normal(2, heads + groups, hd, hd, scale=0.3),
            "conv1_b": normal(heads + groups, hd), "tau": normal(groups),
            "res_a": jnp.stack([1 + normal(d), normal(d), 1 + normal(d),
                                normal(d)]),
            "res_m": jnp.stack([1 + normal(d), normal(d), 1 + normal(d),
                                normal(d)]),
            "r_down": normal(d, r), "r_down_b": normal(r),
            "r_gamma": 1 + normal(r), "r_norm": 1 + normal(r),
            "r_w1": normal(r, r, scale=1.0), "r_b1": normal(r),
            "r_w2": normal(r, r, scale=1.0), "r_b2": normal(r),
            "r_w3": normal(r, 17, scale=2.0),
            "e_gate": normal(held[1] - held[0], d, f),
            "e_up": normal(held[1] - held[0], d, f),
            "e_down": normal(held[1] - held[0], f, d)}

    params = {"wte": normal(vocab, d, scale=1.0), "lnf_g": 1 + normal(d),
              "layers": [layer() for _ in range(layers)]}
    hp = {"eps": 1e-5, "experts_held": held,
          "rope": {"rope_theta": 5e6, "partial_rotary_factor": 0.5}}
    tokens = np.random.default_rng(seed).integers(0, vocab, (2, 25))
    return params, hp, jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])


@pytest.mark.parametrize("by_row", [False, True])
def test_the_pieces_give_the_one_functions_loss_and_gradient(by_row):
    params, hp, tokens, targets = _seeded_reference()
    want, grads = ref.loss_and_grads(params, tokens, targets, hp)
    with jax.default_matmul_precision("highest"):
        got, pieces = ref.Pieces(hp).loss_and_grads(params, tokens, targets,
                                                    by_row=by_row)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    flat_w, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_g = dict(jax.tree_util.tree_flatten_with_path(pieces)[0])
    assert len(flat_w) == len(flat_g) == 2 + 2 * 25
    for path, leaf in flat_w:
        np.testing.assert_allclose(flat_g[path], leaf, rtol=2e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    # layer 0's gain on the incoming state multiplies zeros
    assert not np.asarray(grads["layers"][0]["r_gamma"]).any()
    assert np.asarray(grads["layers"][1]["r_gamma"]).any()


def test_the_references_shares_add_up():
    """The reference itself: experts [0, 8) and [8, 16) and nothing for the
    skip choice give the layer that holds all sixteen."""
    params, hp, tokens, _ = _seeded_reference(seed=3, layers=1, held=(0, 16))
    p = params["layers"][0]
    x = params["wte"][tokens]
    r = jnp.zeros((*tokens.shape, 8))
    with jax.default_matmul_precision("highest"):
        m = ref.rms_norm(x, p["n2"], 1e-5)
        whole, _, _, own = ref.moe(m, r, p, hp)
        parts = []
        for lo in (0, 8):
            share = dict(p, **{k: p[k][lo:lo + 8]
                               for k in ("e_gate", "e_up", "e_down")})
            parts.append(ref.moe(m, r, share,
                                 dict(hp, experts_held=(lo, lo + 8)))[0])
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-6)
    taken = np.asarray(own)
    assert (taken == 16).any() and (taken < 8).any() and (taken >= 8).any()
    skipped = np.asarray(whole)[taken == 16]
    assert not skipped.any()

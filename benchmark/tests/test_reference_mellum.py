"""reference_mellum against the program, in process, on the CPU at the test
size: in float32 the two are the same mathematics and agree to rounding; in
bf16 the comparison's errors sit where the configuration file's tolerances
expect them; and each fault the tolerances are there for — bf16 router
logits, a selection on other scores than the softmax's own, a dropped token,
a window off by one on every row or on three, an unscaled YaRN table — fails
at least one of them. The reference imports nothing from the program."""

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
from lib import check_mellum
from lib import reference_mellum as ref


def _config():
    with open(os.path.join(BENCH, "configs", "mellum-test.json")) as f:
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
    return check_mellum.check(config, bundle, trainer, seed)


TIGHT = dict({f"state_rel_rms_layer_{l}": 2e-5 for l in range(5)},
             state_rel_rms_final=2e-5, token_rel_max=1e-4,
             rope_table_abs=1e-6, router_logits_abs=1e-5,
             window_position_rel_max=2e-5, window_edge_rel_max=2e-5,
             moe_dropped=0, chosen_sets_differ_share=0.0,
             chosen_not_top8_share=0.0, loss_abs=5e-5,
             grad_rel_rms_worst=1e-3)


@pytest.mark.parametrize("seed", [0, 7])
def test_float32_program_equals_the_reference_to_rounding(seed):
    result = _check("float32", jnp.float32, TIGHT, seed=seed)
    assert result["ok"], result
    assert result["errors"]["grad_rel_rms_all"] > 0  # it did compare
    assert 0.6 < result["counters"]["moe_rows_per_token"] < 1.4  # 4 x 4 / 16
    assert 0.25 < result["counters"]["router_chosen_mass"] < 0.5


@pytest.mark.parametrize("seed", [0, 2147483659])
def test_bf16_program_sits_inside_the_files_tolerances(seed):
    result = _check("bfloat16", jnp.bfloat16, seed=seed)
    assert result["ok"], result
    # and not by a mile: bf16 is visible in every layer
    for l in range(5):
        assert result["errors"][f"state_rel_rms_layer_{l}"] > 1e-3
    # the router's float32 arithmetic is not where bf16 shows
    assert result["errors"]["router_logits_abs"] <= 1e-5


def test_a_lower_precision_than_stated_fails():
    result = _check("bfloat16", jnp.bfloat16, TIGHT)
    assert not result["ok"]


def _failing(result):
    return {k for k, tol in result["tolerances"].items()
            if not result["errors"][k] <= tol}


def _softmax_route(real, logits_of=lambda x: x, scores_of=lambda s: s):
    """``ops/moe.py route`` for the softmax form with a fault put in: the
    logits rounded, or the selection made on other scores."""
    def route(h, kernel, k, scaling, softmax=False):
        assert softmax  # the form the description names
        logits = logits_of(real(h, kernel, k, scaling, softmax=True)[0])
        probs = jax.nn.softmax(logits, -1)
        chosen = jax.lax.top_k(scores_of(probs), k)[1]
        top = jnp.take_along_axis(probs, chosen, -1)
        return logits, chosen, scaling * top / jnp.sum(top, -1, keepdims=True)

    return route


def test_bf16_router_logits_fail(monkeypatch):
    from easydl_tpu.ops import moe

    # an explicit rounding: a convert pair may be optimised away
    monkeypatch.setattr(moe, "route", _softmax_route(
        moe.route, logits_of=lambda x: jax.lax.reduce_precision(x, 8, 7)))
    assert "router_logits_abs" in _failing(_check("bfloat16", jnp.bfloat16))


def test_a_biased_score_fed_to_top_k_fails_alone(monkeypatch):
    """A selection on other scores than the softmax's own (a bias that tips
    near-ties on a few tokens in a hundred) stays under the share of sets
    that may differ from the reference's, and under the state limits: the
    reference's layers take the program's sets. Held to its own logits it
    does not pass."""
    from easydl_tpu.ops import moe

    monkeypatch.setattr(moe, "route", _softmax_route(
        moe.route, scores_of=lambda p: p + 2e-4 * (
            jnp.arange(p.shape[-1]) % 2)))
    result = _check("bfloat16", jnp.bfloat16)
    assert _failing(result) == {"chosen_not_top8_share"}, result["errors"]
    assert 0 < result["errors"]["chosen_not_top8_share"] < 0.2


def test_not_top_k_counts_by_the_probabilities_written_out():
    logits = jnp.array([[3.0, 1.0, 2.0, 0.0],    # the two largest: 0, 2
                        [0.5, 0.5, 0.1, 0.5],    # a three-way tie
                        [1.0, 2.0, 3.0, 4.0],
                        [1.0, 2.0, 3.0, 4.0]])
    chosen = jnp.array([[2, 0], [3, 1], [3, 1], [3, 3]])
    # row 1: any two of the tied three; row 2: 1 lies under 2; row 3: twice
    # the same expert
    assert int(check_mellum.not_top_k(logits, chosen)) == 2
    assert int(check_mellum.not_top_k(logits[:2], chosen[:2])) == 0


def test_a_dropped_token_fails(monkeypatch):
    from easydl_tpu.ops import moe

    monkeypatch.setattr(moe, "rows_bound",
                        lambda tokens, k, held: tokens // 4)
    result = _check("bfloat16", jnp.bfloat16)
    assert "moe_dropped" in _failing(result)
    assert result["errors"]["moe_dropped"] > 0


@pytest.mark.parametrize("rows", ["every", "three"])
def test_a_window_off_by_one_fails(monkeypatch, rows):
    """One key too many on every row — or on the three rows behind the
    window's first full row alone (a misplaced mask on one piece, a
    neighbour's edge): the worst POSITION shows both, where a mean over the
    rows shows the first alone."""
    from easydl_tpu.ops import attention

    real = attention._reference_attention

    def wider(q, k, v, *, window=None, **kw):
        if window is None:
            return real(q, k, v, window=None, **kw)
        off = real(q, k, v, window=window + 1, **kw)
        if rows == "every":
            return off
        at = jnp.arange(q.shape[1])[None, :, None, None]
        return jnp.where((at >= window) & (at < window + 3), off,
                         real(q, k, v, window=window, **kw))

    monkeypatch.setattr(attention, "_reference_attention", wider)
    result = _check("bfloat16", jnp.bfloat16)
    assert "window_position_rel_max" in _failing(result), result["errors"]
    # two of the three rows are among the edges the check names: their own
    # worst is past the limit too
    assert result["errors"]["window_edge_rel_max"] \
        > result["tolerances"]["window_position_rel_max"]


def test_an_unscaled_yarn_table_fails(monkeypatch):
    from easydl_tpu.models import transformer

    real = transformer.rope_tables

    def unscaled(seq, head_dim, theta, rot=None, yarn=None):
        if yarn is not None:
            yarn = dict(yarn, attention_factor=1.0)
        return real(seq, head_dim, theta, rot, yarn)

    monkeypatch.setattr(transformer, "rope_tables", unscaled)
    result = _check("bfloat16", jnp.bfloat16)
    assert "rope_table_abs" in _failing(result)
    assert result["errors"]["rope_table_abs"] == pytest.approx(0.277, abs=0.01)


def test_the_gradient_assembled_by_piece_is_jax_grad_of_the_whole_loss():
    """``Pieces.loss_and_grads`` (what the check runs: each piece compiled
    once, the loops over layers and experts in Python, the chain rule over
    the pieces by hand) against ``loss_and_grads`` (``jax.grad`` of the whole
    loss), float32, every leaf; and its layers' states against ``states``."""
    from easydl_tpu.core.sharding import unbox
    from easydl_tpu.models.registry import get_model

    config = _config()
    kwargs = dict(config["kwargs"], seq_len=32, vocab=256, dtype="float32")
    bundle = get_model("mellum", **kwargs)
    plain = check_mellum.to_reference(
        unbox(bundle.init_fn(jax.random.PRNGKey(3))))
    tokens = np.random.default_rng(3).integers(0, 256, (2, 33), np.int32)
    hp = ref.hyper(config)
    whole = ref.loss_and_grads(plain, tokens[:, :-1], tokens[:, 1:], hp)
    pieces = ref.Pieces(hp)
    by_layer = pieces.loss_and_grads(plain, tokens[:, :-1], tokens[:, 1:])
    by_row = pieces.loss_and_grads(plain, tokens[:, :-1], tokens[:, 1:],
                                   by_row=True)
    x = plain["wte"][tokens[:, :-1]]
    for p, kind, want in zip(plain["layers"], hp["layer_types"],
                             ref.states(plain, tokens[:, :-1], hp)):
        x = pieces.layer(x, p, kind)[0]
        np.testing.assert_allclose(np.asarray(x), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    for mine in (by_layer, by_row):
        assert float(mine[0]) == pytest.approx(float(whole[0]), rel=1e-6)
        a, b = jax.tree.leaves(mine[1]), jax.tree.leaves(whole[1])
        assert len(a) == len(b) == 3 + 5 * 10
        for x, y in zip(a, b):
            assert x.shape == y.shape
            assert float(jnp.linalg.norm(x - y)) \
                <= 1e-5 * float(jnp.linalg.norm(y)) + 1e-9


def test_chosen_sets_from_outside_replace_the_references_own():
    """The reference's layer with another's chosen sets: weights from its
    own probabilities at those experts; with its own sets, itself; nothing
    shared, so a token none of whose experts is held gets nothing."""
    config = _config()
    hp = ref.hyper(config)
    assert hp["experts_held"] == (0, 4) and hp["k"] == 4
    d, f = 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    p = {"router": jax.random.normal(ks[0], (d, 16)),
         "e_gate": jax.random.normal(ks[1], (4, d, f)),
         "e_up": jax.random.normal(ks[2], (4, d, f)),
         "e_down": jax.random.normal(ks[3], (4, f, d))}
    m = jax.random.normal(ks[4], (1, 12, d))
    y, logits, own = ref.moe(m, p, hp)
    again, _, _ = ref.moe(m, p, hp, chosen=own)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(again))
    # every token sent to expert 0 and three absent ones: the share of 0
    # among the four's probabilities, the softmax over their logits alone
    forced = jnp.broadcast_to(jnp.array([0, 9, 10, 11], jnp.int32),
                              (1, 12, 4))
    got, _, _ = ref.moe(m, p, hp, chosen=forced)
    w0 = jax.nn.softmax(logits[..., jnp.array([0, 9, 10, 11])], -1)[..., 0]
    want = w0[..., None] * ref.swiglu(m, p["e_gate"][0], p["e_up"][0],
                                      p["e_down"][0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    elsewhere = jnp.broadcast_to(jnp.array([4, 9, 10, 11], jnp.int32),
                                 (1, 12, 4))
    assert not np.asarray(ref.moe(m, p, hp, chosen=elsewhere)[0]).any()


def test_the_band_is_written_out():
    """Query ``i`` sees keys ``(i - window, i]``."""
    mask = np.asarray(ref.band_mask(8, 8, 3))
    for i in range(8):
        assert [j for j in range(8) if mask[i, j]] == [
            j for j in range(8) if i - 3 < j <= i]
    assert np.asarray(ref.band_mask(8, 8, None)).sum() == 36


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "lib", "reference_mellum.py")) as f:
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

"""``lib/reference_sdar.py`` against what it says of itself: the mask from its
four rules entry by entry, the tables' repeated positions, score rows taken a
block of queries at a time, the loss's weights and its missing shift, the
pieces' chain rule against ``jax.grad`` of the whole — and that it imports
nothing from the program."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from lib import reference_sdar as ref


def _hp(**more):
    with open(os.path.join(BENCH, "configs", "sdar-test.json")) as f:
        return dict(ref.hyper(json.load(f)), **more)


def _params(hp, seed=0, vocab=512, d=64, heads=4, groups=2, hd=16, f=32,
            layers=2, experts=16):
    rng = np.random.default_rng(seed)
    lo, hi = hp["experts_held"]

    def normal(*shape, scale=0.05):
        return jnp.asarray(rng.standard_normal(shape, np.float32) * scale)

    return {"wte": normal(vocab, d, scale=1.0), "head": normal(d, vocab),
            "lnf_g": 1 + normal(d), "layers": [
                {"n1": 1 + normal(d), "n2": 1 + normal(d),
                 "wq": normal(d, heads, hd), "wk": normal(d, groups, hd),
                 "wv": normal(d, groups, hd), "wo": normal(heads, hd, d),
                 "qn": 1 + normal(hd), "kn": 1 + normal(hd),
                 "router": normal(d, experts),
                 "e_gate": normal(hi - lo, d, f), "e_up": normal(hi - lo, d, f),
                 "e_down": normal(hi - lo, f, d)} for _ in range(layers)]}


def test_the_reference_imports_nothing_from_the_program():
    for name in ("reference_sdar.py", "flops_sdar.py"):
        with open(os.path.join(BENCH, "lib", name)) as f:
            code = f.read().split('"""', 2)[2]
        assert "easydl_tpu" not in code, name


@pytest.mark.parametrize("seq,block", [(8, 4), (24, 4), (32, 8)])
def test_the_mask_is_its_four_rules(seq, block):
    mask = np.asarray(ref.block_mask(seq, block))
    for q in range(2 * seq):
        for k in range(2 * seq):
            bq, bk = (q % seq) // block, (k % seq) // block
            want = (bk == bq if k < seq else bk < bq) if q < seq \
                else (k >= seq and bk <= bq)
            assert mask[q, k] == want, (q, k)
    assert mask.sum() == seq * seq + seq * block
    assert mask.diagonal().all()


def test_both_halves_carry_the_same_positions():
    cos, sin = ref.rope_tables(16, 8, 1e6)
    assert cos.shape == sin.shape == (32, 8)
    np.testing.assert_array_equal(np.asarray(cos[:16]), np.asarray(cos[16:]))
    np.testing.assert_array_equal(np.asarray(sin[:16]), np.asarray(sin[16:]))
    inv = 1.0 / 1e6 ** (np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(np.asarray(cos[5, :4]), np.cos(5 * inv),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin[21, 4:]), np.sin(5 * inv),
                               atol=1e-6)


def test_score_rows_in_blocks_are_the_whole_matrix():
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal(shape, np.float32))
               for shape in ((2, 32, 4, 8), (2, 32, 2, 8), (2, 32, 2, 8)))
    mask = ref.block_mask(16, 4)
    whole = ref.attention_core(q, k, v, mask, rows=32)
    np.testing.assert_allclose(ref.attention_core(q, k, v, mask, rows=8),
                               whole, atol=1e-6)
    scores = np.einsum("bqhd,bkhd->bhqk", q, np.repeat(k, 2, 2)) / np.sqrt(8)
    scores = np.where(np.asarray(mask), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True),
                     np.repeat(v, 2, 2))
    np.testing.assert_allclose(whole, want, atol=1e-5)


def test_the_loss_weighs_the_masked_positions_own_tokens():
    """No shift, ``masked / t`` a position, the mean over all ``B L``
    positions; the clean half's states feed nothing."""
    hp = _hp()
    params = _params(hp)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 16, 64), np.float32))
    x0 = jnp.asarray(rng.integers(0, 512, (2, 8), dtype=np.int32))
    weights = jnp.asarray(rng.uniform(0, 3, (2, 8)).astype(np.float32))
    got = ref.diffusion_loss(x, params, x0, weights, hp)
    h = ref.rms_norm(x[:, :8], params["lnf_g"], hp["eps"])
    logp = jax.nn.log_softmax(h @ params["head"], -1)
    want = -sum(float(weights[b, i]) * float(logp[b, i, x0[b, i]])
                for b in range(2) for i in range(8)) / 16
    assert float(got) == pytest.approx(want, rel=1e-5)
    moved = x.at[:, 8:].add(1.0)
    assert float(ref.diffusion_loss(moved, params, x0, weights, hp)) \
        == float(got)
    rows = ref.rows_of(x0, weights > 1.5, hp["mask_id"])
    assert rows.shape == (2, 16) and (rows[:, 8:] == x0).all()
    assert ((rows[:, :8] == hp["mask_id"]) == (weights > 1.5)).all()


def test_pieces_assemble_jax_grad_of_the_whole_loss():
    hp = _hp(rows=16)
    params = _params(hp)
    rng = np.random.default_rng(3)
    x0 = jnp.asarray(rng.integers(0, 512, (2, 32), dtype=np.int32))
    t = jnp.repeat(jnp.asarray(rng.uniform(0.05, 1, (2, 8)).astype(
        np.float32)), 4, 1)
    masked = jnp.asarray(rng.uniform(0, 1, (2, 32)).astype(np.float32)) < t
    value, whole = ref.loss_and_grads(params, x0, masked, t, hp)
    mine_value, mine = ref.Pieces(hp).loss_and_grads(params, x0, masked, t)
    assert float(mine_value) == pytest.approx(float(value), abs=1e-6)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(whole)):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * (float(jnp.max(jnp.abs(b))) or 1.0))
    # the chosen sets from outside are the layer's own where they are its own
    pieces = ref.Pieces(hp)
    tokens = ref.rows_of(x0[:1], masked[:1], hp["mask_id"])
    x = params["wte"][tokens]
    y, _, own, _ = pieces.layer(x, params["layers"][0])
    again, _, _, _ = pieces.layer(x, params["layers"][0], own)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(again))

"""``lib/reference_keye.py`` against what it says of itself: the multimodal
rotary's sections written out, the index's scores and its top-k with ties
entry by entry, score rows taken a block of queries at a time, the index
loss's two ``stop_gradient``s, the selection's fault count, the pieces' chain
rule against ``jax.grad`` of the whole, the eight shares of a layer adding up
to the uncut layer — and that it imports nothing from the program; and the
program's own score arithmetic against it on equal inputs."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from lib import reference_keye as ref
from lib.reference_mellum import moe


def _hp(**more):
    with open(os.path.join(BENCH, "configs", "keye-test.json")) as f:
        return dict(ref.hyper(json.load(f)), **more)


def _params(hp, seed=0, vocab=512, d=64, heads=4, groups=2, hd=16, f=32,
            layers=2, experts=16, ix_heads=2, ix_dim=16):
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
                 "iq": normal(d, ix_heads, ix_dim, scale=0.3),
                 "ik": normal(d, ix_dim, scale=0.3),
                 "ik_g": 1 + normal(ix_dim), "ik_b": normal(ix_dim),
                 "iw": normal(d, ix_heads, scale=0.3),
                 "router": normal(d, experts),
                 "e_gate": normal(hi - lo, d, f), "e_up": normal(hi - lo, d, f),
                 "e_down": normal(hi - lo, f, d)} for _ in range(layers)]}


#: a head of 16: eight frequencies in three sections
SMALL = dict(rows=16, topk=24, sections=(2, 3, 3))


def test_the_reference_imports_nothing_from_the_program():
    for name in ("reference_keye.py", "flops_keye.py"):
        with open(os.path.join(BENCH, "lib", name)) as f:
            code = f.read().split('"""', 2)[2]
        assert "easydl_tpu" not in code, name


def test_the_three_sections_with_text_alone_are_the_default_rotary():
    cos, sin = ref.mrope_tables(40, 128, 1e7, (16, 24, 24))
    inv = 1.0 / 1e7 ** (np.arange(0, 128, 2) / 128)
    angles = np.arange(40)[:, None] * inv[None, :]
    np.testing.assert_allclose(cos, np.cos(np.concatenate([angles] * 2, -1)),
                               atol=2e-5)
    np.testing.assert_allclose(sin, np.sin(np.concatenate([angles] * 2, -1)),
                               atol=2e-5)
    plain = ref.rope_tables(40, 128, 1e7)
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(plain[0]))
    with pytest.raises(AssertionError):
        ref.mrope_tables(40, 128, 1e7, (16, 24, 23))


def test_scores_and_the_top_k_with_ties_entry_by_entry():
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(-2, 3, (1, 32, 2, 8)).astype(np.float32))
    b = jnp.asarray(rng.integers(-2, 3, (1, 32, 8)).astype(np.float32))
    w = jnp.asarray(rng.integers(-2, 3, (1, 32, 2)).astype(np.float32)) / 4
    scores = np.asarray(ref.index_scores(a, b, w))[0]
    for t in (0, 5, 31):
        for s in (0, 3, 17):
            want = sum(float(w[0, t, j]) * max(float(a[0, t, j] @ b[0, s]), 0)
                       for j in range(2))
            assert scores[t, s] == pytest.approx(want)
    chosen = np.asarray(ref.select(jnp.asarray(scores[None, 8:24]), 8, 6))[0]
    for i, t in enumerate(range(8, 24)):
        order = sorted(range(t + 1), key=lambda s: (-scores[t, s], s))[:6]
        assert sorted(np.flatnonzero(chosen[i])) == sorted(order), t
    assert len({x for x in scores[20, :21]}) < 21  # there are ties


def test_score_rows_in_blocks_are_the_whole_matrix():
    rng = np.random.default_rng(2)
    q, k, v, a, b, w = (jnp.asarray(rng.standard_normal(shape, np.float32))
                        for shape in ((2, 64, 4, 8), (2, 64, 2, 8),
                                      (2, 64, 2, 8), (2, 64, 2, 8),
                                      (2, 64, 8), (2, 64, 2)))
    hp = {"rows": 64, "topk": 24}
    whole, kl, _ = ref.indexed_attention(q, k, v, a, b, w, hp)
    cut, kl_cut, _ = ref.indexed_attention(q, k, v, a, b, w,
                                           dict(hp, rows=16))
    np.testing.assert_allclose(cut, whole, atol=1e-6)
    assert float(kl_cut) == pytest.approx(float(kl), rel=1e-5)
    # written out in numpy
    chosen = np.asarray(ref.select(ref.index_scores(a, b, w), 0, 24))
    assert (chosen.sum(-1) == np.minimum(np.arange(64) + 1, 24)).all()
    scores = np.einsum("bqhd,bkhd->bhqk", q, np.repeat(k, 2, 2)) / np.sqrt(8)
    scores = np.where(chosen[:, None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        whole, np.einsum("bhqk,bkhd->bqhd", p, np.repeat(v, 2, 2)), atol=1e-5)
    index = np.where(chosen, np.asarray(ref.index_scores(a, b, w)), -np.inf)
    log_i = index - index.max(-1, keepdims=True)
    log_i = log_i - np.log(np.exp(log_i).sum(-1, keepdims=True))
    mean = p.mean(1)
    want = np.where(chosen & (mean > 0), mean * (
        np.log(np.where(mean > 0, mean, 1)) - np.where(chosen, log_i, 0)),
        0).sum()
    assert float(kl) == pytest.approx(want, rel=1e-4)
    # the sets from outside replace the own ones, and are counted against them
    other = np.roll(chosen, 1, axis=0)
    _, _, differ = ref.indexed_attention(q, k, v, a, b, w, hp,
                                         jnp.asarray(other))
    assert int(differ) == int((other != chosen).sum()) > 0


def test_the_two_stop_gradients():
    hp = _hp(**SMALL)
    params = _params(hp)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, 512, (1, 65), dtype=np.int32))

    def part(i):
        return jax.grad(lambda p: ref.loss(p, tokens[:, :-1], tokens[:, 1:],
                                           hp)[1][i])(params)

    main, own = part(0), part(1)
    index = ("iq", "ik", "ik_g", "ik_b", "iw")
    for layer_main, layer_own in zip(main["layers"], own["layers"]):
        for name in layer_main:
            mine, other = (layer_own, layer_main) if name in index \
                else (layer_main, layer_own)
            assert not np.asarray(other[name]).any(), name
            assert np.asarray(mine[name]).any(), name
    for name in ("wte", "head", "lnf_g"):
        assert not np.asarray(own[name]).any(), name


def test_selection_faults_finds_what_a_mean_would_hide():
    rng = np.random.default_rng(4)
    a, b, w = (jnp.asarray(rng.standard_normal(shape, np.float32))
               for shape in ((1, 64, 2, 8), (1, 64, 8), (1, 64, 2)))
    scores = ref.index_scores(a, b, w)
    good = ref.select(scores, 0, 24)
    margin = 32 * 2.0 ** -24

    def faults(chosen, topk=24):
        return int(ref.selection_faults(a, b, w, chosen, topk, margin,
                                        rows=16))

    assert faults(good) == 0
    assert faults(good, 23) == 64 - 23   # one key too many from row 23 on
    assert faults(good, 25) == 64 - 24   # one too few from row 24 on
    assert faults(good.at[0, 5, 9].set(True)) >= 1       # a key from ahead
    swapped = good.at[0, 40].set(ref.select(-scores, 0, 24)[0, 40])
    assert faults(swapped) == 1                           # the smallest
    rounded = ref.select(scores.astype(jnp.bfloat16).astype(jnp.float32),
                         0, 24)
    assert faults(rounded) > 0     # ranked on scores rounded to bf16


def test_pieces_assemble_jax_grad_of_the_whole_loss():
    hp = _hp(**SMALL)
    params = _params(hp)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, 512, (2, 65), dtype=np.int32))
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    (value, (main, own)), whole = ref.loss_and_grads(params, inputs, targets,
                                                     hp)
    pieces = ref.Pieces(hp)
    (mine_value, (mine_main, mine_own)), mine = pieces.loss_and_grads(
        params, inputs, targets)
    assert float(mine_value) == pytest.approx(float(value), abs=2e-6)
    assert float(mine_own) == pytest.approx(float(own), abs=2e-6) and own > 0
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(whole)):
        np.testing.assert_allclose(
            a, b, atol=3e-5 * (float(jnp.max(jnp.abs(b))) or 1.0))
    # the sets from outside are the layer's own where they are its own
    x = params["wte"][inputs[:1]]
    p = params["layers"][0]
    y, _, own_sets, _, kl, _ = pieces.layer(x, p)
    a, b, w = ref.index_inputs(ref.rms_norm(x, p["n1"], hp["eps"]), p, hp)
    ranked = ref.select(ref.index_scores(a, b, w), 0, hp["topk"])
    again, _, _, _, kl_again, differ = pieces.layer(x, p, own_sets, ranked)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(again))
    assert float(kl) == float(kl_again) and int(differ) == 0


def test_eight_shares_add_up_to_the_uncut_layer():
    """Attention, index and router whole on every chip and counted once,
    each share's routed part added: the uncut reference's layer."""
    hp = _hp(experts_held=(0, 16), **SMALL)
    params = _params(hp, seed=6, layers=1)
    p = params["layers"][0]
    x = params["wte"][jnp.asarray(np.random.default_rng(7).integers(
        0, 512, (1, 64), dtype=np.int32))]
    whole, _, own, _ = ref.layer(x, p, hp)
    mid, _, _ = ref.attention_residual(x, p, hp)
    m = ref.rms_norm(mid, p["n2"], hp["eps"])
    total, per_share = mid, []
    for rank in range(8):
        lo, hi = 2 * rank, 2 * rank + 2
        share = dict(p, **{name: p[name][lo:hi]
                           for name in ("e_gate", "e_up", "e_down")})
        part = moe(m, share, dict(hp, experts_held=(lo, hi)), own)[0]
        per_share.append(float(jnp.sum(jnp.abs(part))))
        total = total + part
    assert all(size > 0 for size in per_share)
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_the_programs_score_arithmetic_on_equal_inputs():
    """``ops/index.py``'s written-out scores (what its XLA path ranks) and
    its selection against the reference's, on the same float32 inputs."""
    from easydl_tpu.ops import index

    rng = np.random.default_rng(8)
    a, b, w = (jnp.asarray(rng.standard_normal(shape, np.float32))
               for shape in ((1, 256, 2, 64), (1, 256, 64), (1, 256, 2)))
    with jax.default_matmul_precision("highest"):
        mine = index.scores_reference(a, b, w)
    want = ref.index_scores(a, b, w)
    np.testing.assert_allclose(mine, want, atol=1e-5)
    words, _, _ = index.select(a, b, w, topk=96, kernels=False)
    chosen = index.unpack(words)
    assert int(ref.selection_faults(a, b, w, chosen, 96, 1e-5)) == 0
    assert float(jnp.mean(chosen == ref.select(want, 0, 96))) > 0.999

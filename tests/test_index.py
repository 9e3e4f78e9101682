"""A learned index in front of attention (``ops/index.py``; the ``select``
operand of ``ops/flash_attention.py``; ``ops/attention.py
indexed_attention``; ``models/keye.py``): the selection against a written-out
``[L, L]`` ranking with ties, the kernels (interpreted, at the smallest shape
that crosses a tile, a key chunk and a query cell) against the XLA path under
the written-out mask entry by entry, the two ``stop_gradient``s, the
selection across rematerialisation, and the two reductions the model's
description rests on (text-only multimodal rotary; ``t < topk`` is causal)."""

from __future__ import annotations

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydl_tpu.core import sharding as shd
from easydl_tpu.models.registry import get_model
from easydl_tpu.ops import index
from easydl_tpu.ops.attention import indexed_attention, multihead_attention
from easydl_tpu.ops.rope import rope_tables

SEQ, TOPK = 512, 96
HEADS, KV, DIM = 2, 1, 128
IX_HEADS, IX_DIM = 2, 64


def _inputs(seed: int, whole: bool, seq: int = SEQ):
    """q, k, v and the index's a, b, w; ``whole``: the index's are small
    integers (over 4 for w), so that every product is exact on both paths
    and TIES are many."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (1, seq, HEADS, DIM))
    k = jax.random.normal(ks[1], (1, seq, KV, DIM))
    v = jax.random.normal(ks[2], (1, seq, KV, DIM))
    a = jax.random.normal(ks[3], (1, seq, IX_HEADS, IX_DIM))
    b = jax.random.normal(ks[4], (1, seq, IX_DIM))
    w = jax.random.normal(ks[5], (1, seq, IX_HEADS)) * 0.1
    if whole:
        a, b, w = jnp.round(a), jnp.round(b), jnp.round(w * 20) / 4
    return q, k, v, a, b, w


def _ranked(scores: np.ndarray, topk: int) -> np.ndarray:
    """The selection written out in Python: per query the ``min(t + 1,
    topk)`` causal keys of largest score, ties to the lower key."""
    seq = scores.shape[-1]
    chosen = np.zeros((seq, seq), bool)
    for t in range(seq):
        order = sorted(range(t + 1), key=lambda s: (-scores[t, s], s))
        chosen[t, order[:topk]] = True
    return chosen


def test_pack_and_unpack_are_inverse_and_count_tiles():
    dense = jnp.tril(jax.random.bernoulli(jax.random.PRNGKey(0), 0.3,
                                          (2, SEQ, SEQ)))
    words = index.pack(dense)
    assert words.shape == (2, SEQ // 32, SEQ) and words.dtype == jnp.int32
    assert bool(jnp.all(index.unpack(words) == dense))
    tiles = dense.reshape(2, SEQ // 128, 128, SEQ // 128, 128).any((2, 4))
    assert float(index.live_tiles(words)) == float(tiles.sum())
    assert index.tiles(SEQ) == 10
    assert index.selected_pairs(16384, 2048) == 31_458_304
    assert index.causal_pairs(16384) == 134_225_920


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("topk", [TOPK, 300])
def test_selection_is_the_written_out_ranking_with_ties(kernels, topk):
    """Scores and top-k against an ``[L, L]`` computation, ``topk < L``, on
    inputs whose products are exact: thousands of pairs tie (a relu's zeros
    among them) and go to the lower key on both paths; the kernel takes two
    query cells and two key chunks of 256."""
    _, _, _, a, b, w = _inputs(1, whole=True)
    scores = np.asarray(index.scores_reference(a, b, w))[0]
    causal = np.tril(np.ones((SEQ, SEQ), bool))
    ties = sum(len(row[:t + 1]) - len(set(row[:t + 1]))
               for t, row in enumerate(scores))
    assert ties > 1000
    words, lse, squares = index.select(a, b, w, topk=topk, kernels=kernels,
                                       chunk=256, interpret=True)
    chosen = np.asarray(index.unpack(words))[0]
    want = _ranked(scores, topk)
    assert (chosen == want).all()
    assert (chosen.sum(-1) == np.minimum(np.arange(SEQ) + 1, topk)).all()
    picked = np.where(want, scores, -np.inf)
    top = picked.max(-1)
    np.testing.assert_allclose(
        lse[0], top + np.log(np.exp(picked - top[:, None]).sum(-1)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        squares[0], np.where(causal, scores ** 2, 0.0).sum(-1), rtol=1e-5)


def _objective(impl, topk=TOPK):
    def f(q, k, v, a, b, w, g):
        out, loss, stats = indexed_attention(
            q, k, v, a, b, w, topk=topk, impl=impl, chunk=256,
            interpret=True)
        return loss + jnp.sum(out * g), (out, loss, stats)
    return f


def test_kernels_match_the_xla_path_under_the_written_out_mask():
    """``dsa_fwd`` / ``dsa_bwd`` / ``index_kl`` (interpreted) against XLA's
    operations under the SAME selection written out: the result, the loss
    and every gradient — q, k, v through the attention, a, b, w through the
    index's loss — entry by entry."""
    args = _inputs(2, whole=False)
    g = jax.random.normal(jax.random.PRNGKey(9), args[0].shape) * 0.01
    got, want = (jax.jit(jax.value_and_grad(
        _objective(impl), argnums=range(6), has_aux=True))(*args, g)
        for impl in ("flash", "reference"))
    (_, (out, loss, stats)), grads = got
    (_, (out_r, loss_r, stats_r)), grads_r = want
    assert bool(jnp.all(stats["words"] == stats_r["words"]))
    assert float(stats["live_tiles"]) == float(stats_r["live_tiles"]) == 10
    np.testing.assert_allclose(out, out_r, atol=2e-5)
    np.testing.assert_allclose(loss, loss_r, rtol=1e-5)
    for name, mine, theirs in zip("qkvabw", grads, grads_r):
        scale = float(jnp.max(jnp.abs(theirs)))
        assert scale > 0, name
        np.testing.assert_allclose(mine, theirs, atol=2e-4 * scale,
                                   err_msg=name)
    # the attention sees the index through the selection alone, the index's
    # loss the attention through detached values alone
    _, only_loss = jax.jit(jax.value_and_grad(
        lambda *x: _objective("flash")(*x)[1][1], argnums=range(6)))(*args, g)
    for name, grad in zip("qkv", only_loss[:3]):
        assert not np.asarray(grad).any(), name


def _kl_case(name, seq=SEQ, dtype=jnp.float32, tol=2e-4, whole=False,
             zeroed=False):
    """A case of the test below: 512 rows, float32 operands held to 2e-4 of
    a gradient's largest entry and inputs as drawn, but for what it names."""
    return pytest.param(seq, dtype, tol, whole, zeroed, id=name)


@pytest.mark.parametrize("seq, dtype, tol, whole, zeroed", [
    _kl_case("float32"),
    _kl_case("bfloat16", dtype=jnp.bfloat16, tol=1e-2),
    _kl_case("a-head-weighs-nothing-on-some-queries", zeroed=True),
    _kl_case("blocks-finish-after-dead-chunks", seq=768),
    _kl_case("scores-of-exactly-zero", whole=True)])
def test_index_kl_is_the_written_out_loss_and_its_gradients(
        seq, dtype, tol, whole, zeroed, monkeypatch):
    """``index_kl`` alone (interpreted; query blocks of 256, key chunks of
    256) against ``_kl_reference`` in float32 on the SAME values and under
    the same selection: the loss and ``da``, ``db``, ``dw`` entry by entry.
    A tile's relu(scores) are made once and kept, ``w`` meets the sums a
    query block at a time and ``dw`` comes from the query gradient's
    accumulator — so: bf16 operands (the products read ``bf16(dI)`` and
    ``bf16(w * a)``) beside float32's; weights of both signs and, on every
    third query, one of exactly zero, whose ``da`` is zero and whose ``dw``
    is not (nothing is divided by ``w``); three query blocks whose finish
    runs at the grid's last program, up to two dead chunks after their last
    live one; and scores of exactly 0.0 (whole-number inputs), through which
    no gradient goes."""
    q, k, _, a, b, w = _inputs(5, whole, seq)
    assert bool((w < 0).any()) and bool((w > 0).any())
    if zeroed:
        w = w.at[:, ::3, 0].set(0.0)
    a, b, q, k = (x.astype(dtype) for x in (a, b, q, k))
    scale = DIM ** -0.5
    words, lse_i, _ = index.select(a, b, w, topk=TOPK, kernels=False,
                                   chunk=256)
    chosen = index.unpack(words)
    a32, b32, q32, k32 = (x.astype(jnp.float32) for x in (a, b, q, k))
    s = jnp.einsum("bthd,bshd->bhts", q32,
                   jnp.repeat(k32, HEADS // KV, axis=2)) * scale
    lse = jax.nn.logsumexp(jnp.where(chosen[:, None], s, -jnp.inf), axis=-1)
    if whole:
        # the reference's relu gives a zero no gradient either (``maximum``
        # would halve it), and the case is reached: selected pairs at 0.0
        monkeypatch.setattr(
            index, "scores_reference", lambda a, b, w: jnp.einsum(
                "bhts,bth->bts", jax.nn.relu(jnp.einsum(
                    "bthd,bsd->bhts", a, b)), w) + 0.0)
        zeros = (jnp.einsum("bthd,bsd->bhts", a32, b32) == 0.0) \
            & chosen[:, None]
        assert int(zeros.sum()) > 100

    (loss, grads), (loss_r, grads_r) = (
        jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(*x) for f, x in (
            (lambda a, b, w: index.kl(
                a, b, w, q, k, lse, words, lse_i, scale=scale, kernels=True,
                chunk=256, interpret=True), (a, b, w)),
            (lambda a, b, w: index._kl_reference(
                a, b, w, q32, k32, words, scale), (a32, b32, w))))
    np.testing.assert_allclose(loss, loss_r, rtol=1e-5)
    for name, mine, theirs in zip(("da", "db", "dw"), grads, grads_r):
        assert mine.dtype == (jnp.float32 if name == "dw" else dtype), name
        largest = float(jnp.max(jnp.abs(theirs)))
        assert largest > 0, name
        np.testing.assert_allclose(mine.astype(jnp.float32), theirs,
                                   atol=tol * largest, err_msg=name)
    if zeroed:
        da, _, dw = (np.asarray(x) for x in grads)
        assert not da[:, ::3, 0].any()
        assert (dw[:, ::3, 0] != 0).mean() > 0.9


def test_a_pair_outside_the_selection_adds_nothing():
    """Values at the keys a query did NOT select may be anything: the
    result does not move, on the kernels as on the written-out mask."""
    q, k, v, a, b, w = _inputs(3, whole=False)
    words, _, _ = index.select(a, b, w, topk=TOPK, kernels=False)
    seen_by_any = np.asarray(index.unpack(words))[0].any(0)
    assert not seen_by_any.all()
    poison = jnp.where(seen_by_any[None, :, None, None], v, 1e4)
    for impl in ("flash", "reference"):
        out = indexed_attention(q, k, v, a, b, w, topk=TOPK, impl=impl,
                                chunk=256, interpret=True)[0]
        moved = indexed_attention(q, k, poison, a, b, w, topk=TOPK, impl=impl,
                                  chunk=256, interpret=True)[0]
        assert bool(jnp.all(out == moved)), impl


def test_fewer_rows_than_topk_is_plain_causal_attention_bit_for_bit():
    q, k, v, a, b, w = _inputs(4, whole=False)
    out = indexed_attention(q, k, v, a, b, w, topk=SEQ, impl="reference")[0]
    plain = multihead_attention(q, k, v, causal=True, impl="reference")
    assert bool(jnp.all(out == plain))


def test_text_only_multimodal_rotary_is_the_default_table():
    """``mrope_section`` [16, 24, 24] of a head of 128: frequency ``i`` takes
    the position stream of its section; with text alone the three streams
    are equal and the tables are ``rope_tables``'s."""
    seq, dim, theta, sections = 64, 128, 1e7, (16, 24, 24)
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    streams = np.stack([np.arange(seq, dtype=np.float32)] * 3)
    of = np.repeat(np.arange(3), sections)  # a frequency's stream
    angles = streams[of].T * inv[None, :]
    angles = np.concatenate([angles, angles], -1)
    cos, sin_signed = rope_tables(seq, dim, theta)
    sign = np.where(np.arange(dim) < dim // 2, -1.0, 1.0)
    np.testing.assert_allclose(cos, np.cos(angles), atol=2e-5)
    np.testing.assert_allclose(sin_signed * sign, np.sin(angles), atol=2e-5)


KEYE = dict(size="test", seq_len=256, vocab=512,
            layer_types=["full_attention"] * 2, experts_held=(0, 4))


@pytest.fixture(scope="module")
def keye():
    bundle = get_model("keye", **KEYE)
    params = shd.unbox(bundle.init_fn(jax.random.PRNGKey(0)))
    tokens = np.random.default_rng(0).integers(0, 512, (2, 257)).astype(
        np.int32)
    return bundle, params, {"inputs": tokens[:, :-1],
                            "targets": tokens[:, 1:]}


def _by_leaf(tree):
    return flax.traverse_util.flatten_dict(
        jax.tree.map(lambda x: float(jnp.max(jnp.abs(x))), tree), sep="/")


def test_the_two_stop_gradients_are_exact(keye):
    """The next-token loss's gradient on the index's leaves is exactly zero,
    the index loss's on every other leaf exactly zero, and the objective is
    their sum."""
    bundle, params, batch = keye
    rng = jax.random.PRNGKey(1)

    def part(name):
        return jax.value_and_grad(
            lambda p: bundle.loss_fn(p, batch, rng)[1][name])

    # ONE program: the two parts' gradients and the objective
    (main, g_main), (own, g_own), (loss, metrics) = jax.jit(lambda p: (
        part("loss_main")(p), part("index_loss")(p),
        bundle.loss_fn(p, batch, rng)))(params)
    assert float(loss) == pytest.approx(float(main) + float(own), rel=1e-6)
    assert float(own) > 0
    assert float(metrics["index_selected_pairs"]) == \
        index.selected_pairs(256, 96)
    assert float(metrics["index_live_tiles"]) == \
        float(metrics["index_tiles"]) == 2 * 2 * 3
    main_by, own_by = _by_leaf(g_main), _by_leaf(g_own)
    assert sum("index" in name for name in main_by) == 5
    for name in main_by:
        mine, other = (own_by, main_by) if "index" in name \
            else (main_by, own_by)
        assert other[name] == 0.0, name
        assert mine[name] > 0.0, name


def test_the_selection_is_kept_across_rematerialisation(keye, monkeypatch):
    """Under remat ``full`` the second forward ranks nothing again: the
    compiled gradient holds two sorts fewer (the ranking's two, in the
    backward scan's body) than under a policy that does not save the
    selection's name — and the gradient is the unremat one's to the bit."""
    from easydl_tpu.ops import remat

    bundle, params, batch = keye
    rng = jax.random.PRNGKey(1)

    def gradient(bundle):
        return jax.jit(jax.grad(
            lambda p: bundle.loss_fn(p, batch, rng)[0])).lower(
                params).compile()

    def sorts(compiled):
        return compiled.as_text().count(" sort(")

    kept = gradient(get_model("keye", remat=True, **KEYE))
    monkeypatch.setattr(remat, "_FULL", jax.checkpoint_policies
                        .save_only_these_names(*(
                            name for name in remat.KEPT["full"]
                            if name != remat.SELECTED)))
    assert sorts(gradient(get_model("keye", remat=True, **KEYE))) \
        == sorts(kept) + 2
    monkeypatch.undo()
    for (path, mine), theirs in zip(
            jax.tree_util.tree_leaves_with_path(kept(params)),
            jax.tree.leaves(gradient(bundle)(params))):
        assert bool(jnp.all(mine == theirs)), jax.tree_util.keystr(path)


def test_what_an_index_stands_on_is_refused_elsewhere():
    from easydl_tpu.models.keye import describe
    import dataclasses

    cfg = describe(**KEYE)
    with pytest.raises(NotImplementedError, match="a learned index with"):
        dataclasses.replace(cfg, causal=False)
    with pytest.raises(ValueError, match="whole groups of 256"):
        index.select(*(x[:, :128] for x in _inputs(0, True)[3:]), topk=8,
                     kernels=False)
    assert cfg.param_count == sum(
        x.size for x in jax.tree.leaves(jax.eval_shape(
            get_model("keye", **KEYE).init_fn, jax.random.PRNGKey(0))))

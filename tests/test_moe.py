"""The expert layer (``ops/moe.py``): routing invariants, no token dropped,
forward and gradients, the grouped products and the experts' rule. (The
models that hold it train over an ep-sharded mesh in their own files:
``tests/test_laguna.py``, ``tests/test_joyai.py``, ``tests/test_zaya.py``.)"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import normal

from easydl_tpu.core import sharding as shd
from easydl_tpu.ops import moe
from easydl_tpu.ops.moe import (COUNTERS, MoeMlp, chunk_rows, grouped_rows,
                                grouped_weights, piece_rows, route,
                                routed_experts, rows_bound)


def test_routing_invariants():
    tokens, d, total, k = 64, 16, 8, 3
    h, kernel = normal(0, (tokens, d), (d, total))
    logits, chosen, weights = jax.jit(route, static_argnums=(2, 3))(
        np.asarray(h).astype(jnp.bfloat16), kernel, k, 2.5)
    assert logits.dtype == jnp.float32 and logits.shape == (tokens, total)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    # k distinct experts a token, the k largest sigmoid scores
    assert all(len(set(row)) == k for row in chosen)
    scores = 1 / (1 + np.exp(-np.asarray(logits)))
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(-scores, -1)[:, :k], -1))
    # renormalised over the chosen, times the scaling; each its score's share
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    picked = np.take_along_axis(scores, chosen, -1)
    np.testing.assert_allclose(weights, 2.5 * picked / picked.sum(-1,
                                                                  keepdims=True),
                               rtol=1e-5)
    # at most min(k, held) of a token's choices fall on a share
    assert rows_bound(tokens, k, 2) == 2 * tokens
    assert rows_bound(tokens, k, 8) == k * tokens


def test_routing_drops_nothing():
    """Every token chooses ONE expert, the same one: all of them get a row
    (the old layer kept ``capacity`` of them and dropped the rest)."""
    tokens, d, f, held = 32, 8, 4, 4
    h, = normal(0, (tokens, d))
    chosen = jnp.zeros((tokens, 1), jnp.int32)
    weights = jnp.ones((tokens, 1), jnp.float32)
    w_gate, w_up, w_down = _held_experts(held, d, f)
    routed = jax.jit(routed_experts, static_argnums=(6, 7))
    y, stats = routed(h, chosen, weights, w_gate, w_up, w_down, 0, 8)
    dropped, mine, _, largest = (float(x) for x in stats)
    assert (dropped, mine, largest) == (0.0, tokens, tokens)
    want = jax.jit(lambda h, g, u, d: (jax.nn.silu(h @ g) * (h @ u)) @ d)(
        h, w_gate[0], w_up[0], w_down[0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    # a share that holds none of the chosen experts adds exactly nothing
    y, stats = routed(h, chosen, weights, w_gate, w_up, w_down, 4, 8)
    assert not np.asarray(y).any() and float(stats[1]) == 0.0


@pytest.mark.parametrize("tokens, k, held, total, want", [
    (16384, 8, 32, 256, 32768),     # Laguna's cell: a quarter of the bound
    (16384, 8, 256, 256, 131072),   # all experts held: the bound, one piece
    (32, 2, 8, 8, 64),              # the tier-1 configurations likewise
    (128, 2, 4, 16, 128),           # an ep shard's own: 4 of 16, half its bound
    (1024, 4, 4, 32, 1024),         # four pieces to the bound
    (1000, 4, 4, 32, 1024),         # whole row tiles
    (32, 1, 4, 8, 32),              # never more than the bound
])
def test_piece_is_twice_the_expected_load(tokens, k, held, total, want):
    assert piece_rows(tokens, k, held, total) == want
    assert want <= rows_bound(tokens, k, held)


def _held_experts(held, d=16, f=8):
    return normal(1, (held, d, f), (held, d, f), (held, f, d))


def _tokens_and_weights(seed, tokens, k, d=16):
    """A state a token (normal) and a weight a choice (uniform)."""
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((tokens, d), np.float32)),
            jnp.asarray(rng.random((tokens, k), np.float32)))


@jax.jit
def _loop_value_and_grads(chosen, args):
    """Value and the five gradients of the loop over the experts: one
    program a set of shapes (``chosen`` is an argument, not a constant, so
    cases that differ in the choices alone share it)."""
    return jax.value_and_grad(
        lambda *a: _weighed(_loop_over_experts(a[0], chosen, *a[1:])),
        argnums=(0, 1, 2, 3, 4))(*args)


def _loop_over_experts(h, chosen, weights, w_gate, w_up, w_down):
    """The routed result as a loop over the experts held (numbered from 0),
    every token through every expert, float32."""
    y = jnp.zeros_like(h)
    for e in range(w_gate.shape[0]):
        weight = jnp.where(chosen == e, weights, 0.0).sum(-1)
        y = y + weight[:, None] * (
            (jax.nn.silu(h @ w_gate[e]) * (h @ w_up[e])) @ w_down[e])
    return y


def _weighed(y):
    """A scalar of ``y`` whose gradient differs from entry to entry."""
    return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()


@functools.partial(jax.jit, static_argnums=1)
def _routed_value_and_grads(chosen, total, args):
    def loss(h, weights, w_gate, w_up, w_down):
        y, stats = routed_experts(h, chosen, weights, w_gate, w_up, w_down,
                                  0, total)
        return _weighed(y), stats

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                              has_aux=True)(*args)


def _value_stats_grads(chosen, total, args):
    """``(loss, stats, gradients by h, weights and the three expert
    weights)`` of the layer's routed part under a fixed cotangent: one
    program a set of shapes and ``total`` (the choices are an argument)."""
    (value, stats), grads = _routed_value_and_grads(chosen, total, args)
    return value, np.asarray(stats), grads


def _landing(tokens, k, held, total, landed):
    """``chosen [tokens, k]``: distinct experts a token, exactly ``landed``
    choices on the ``held`` first experts, spread evenly over the tokens."""
    chosen = held + (np.arange(tokens)[:, None] + np.arange(k)) % (total - held)
    for i in range(landed):
        chosen[i % tokens, i // tokens] = (i + i // tokens) % held
    assert all(len(set(row)) == k for row in chosen)
    assert (chosen < held).sum() == landed
    return jnp.asarray(chosen, jnp.int32)


# 1,024 tokens, 4 choices, 4 of 32 experts held: pieces of 1,024 rows, a
# bound of 4,096
@pytest.mark.parametrize("landed, pieces", [
    (0, 0), (1024, 1), (1025, 2), (1700, 2), (4096, 4)])
def test_pieces_match_one_piece_and_a_loop_over_experts(landed, pieces):
    """Whatever lands on the experts held — nothing, a piece exactly, one
    row more, every choice of every token (four pieces) — has a row, and the
    result and every gradient are those of the one-piece program (all
    experts held: the piece is the bound) on the same rows, and of a loop
    over the experts."""
    tokens, k, held, total = 1024, 4, 4, 32
    piece = piece_rows(tokens, k, held, total)
    chosen = _landing(tokens, k, held, total, landed)
    args = (*_tokens_and_weights(0, tokens, k), *_held_experts(held))
    value, stats, grads = _value_stats_grads(chosen, total, args)
    dropped, mine, overflow, largest = stats
    assert (dropped, mine) == (0.0, landed)
    assert overflow == float(pieces > 1) and -(-landed // piece) == pieces
    assert largest == np.bincount(np.asarray(chosen).ravel(),
                                  minlength=held)[:held].max()

    # choices elsewhere are no expert of the one-piece program's (-1)
    whole, stats_whole, grads_whole = _value_stats_grads(
        jnp.where(chosen < held, chosen, -1), held, args)
    assert piece_rows(tokens, k, held, held) == rows_bound(tokens, k, held)
    assert tuple(stats_whole[:3]) == (0.0, landed, 0.0)
    want, grads_want = _loop_value_and_grads(chosen, args)
    for got, one_piece, looped in zip((value, *grads), (whole, *grads_whole),
                                      (want, *grads_want)):
        scale = max(float(jnp.abs(looped).max()), 1.0)
        np.testing.assert_allclose(got, one_piece, rtol=0, atol=2e-6 * scale)
        np.testing.assert_allclose(got, looped, rtol=0, atol=2e-6 * scale)


# ------------------------------------------------- a piece's live chunks
@contextlib.contextmanager
def _pieces_traced_anew(**stand_ins):
    """``ops/moe.py`` with ``stand_ins`` in place of its functions, the
    jitted pieces' traces dropped on the way in and out (they are cached by
    shape, whatever the module's functions were when they were made)."""
    kept = {name: getattr(moe, name) for name in stand_ins}
    try:
        for name, stand_in in stand_ins.items():
            setattr(moe, name, stand_in)
        moe._piece_forward.clear_cache(), moe._piece_backward.clear_cache()
        yield
    finally:
        for name, was in kept.items():
            setattr(moe, name, was)
        moe._piece_forward.clear_cache(), moe._piece_backward.clear_cache()


def _whole_pieces():
    """The parent's form (PR 45), kept as the reference: every gather
    between a piece's kernels runs over the whole static piece."""
    return _pieces_traced_anew(
        _live_rows=lambda source, at, n_live, interpret: source[at])


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _part_value_and_grads(form, chosen, total, shards, args):
    """``((loss, (y, stats)), gradients by every argument)`` of the routed
    part on ``args = (h, weights, *experts)`` (two expert leaves: relu2),
    under the context mesh's ``ep`` shards (``shards`` of them: a key of
    the trace, as ``form``, which tells the two forms' traces apart and no
    more): one program a form and set of shapes (the choices are an
    argument)."""
    part = functools.partial(moe._routed_part, total=total)
    tokens, held = chosen.shape[0], args[2].shape[0]

    def loss(h, weights, *experts):
        y, stats = moe._over_expert_shards(part, tokens, held)(
            h, chosen, weights, experts, jnp.int32(0))
        return _weighed(y.astype(jnp.float32)), (y, stats)

    return jax.value_and_grad(loss, argnums=tuple(range(len(args))),
                              has_aux=True)(*args)


def _both_forms(chosen, total, args, shards=1, **stand_ins):
    """The layer over its live chunks (with ``stand_ins``) and over whole
    pieces: ``(got, want)``, each ``_part_value_and_grads``' result."""
    def mesh():
        return jax.sharding.set_mesh(jax.make_mesh(
            (shards,), ("ep",), (jax.sharding.AxisType.Auto,),
            devices=jax.devices()[:shards])) \
            if shards > 1 else contextlib.nullcontext()

    with mesh(), _whole_pieces():
        want = _part_value_and_grads("whole", chosen, total, shards, args)
    with mesh(), _pieces_traced_anew(**stand_ins):
        got = _part_value_and_grads(("chunks", *stand_ins), chosen, total,
                                    shards, args)
    return got, want


def _live_chunk_args(dtype, gated, tokens=1024, k=4, held=4):
    h, weights = _tokens_and_weights(7, tokens, k)
    experts = _held_experts(held)[0 if gated else 1:]
    return (h.astype(dtype), weights,
            *(jnp.asarray(0.3 * np.asarray(w)).astype(dtype)
              for w in experts))


def _assert_same_bits(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# 1,024 tokens, 4 choices, 4 of 32 experts held: pieces of 1,024 rows in
# chunks of 128, a bound of 4,096
@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("landed, made", [
    (0, 0),                 # nothing lands: no chunk is made
    (1, 128),               # one row: one chunk
    (383, 384), (384, 384), (385, 512),  # a chunk's edge less one, it, one more
    (512, 512),             # the expected load
    (1024, 1024),           # a full piece
    (1025, 1024 + 128),     # two pieces, the second with one live chunk
])
def test_live_chunks_are_whole_pieces_bit_for_bit(landed, made, dtype, gated):
    """The layer whose HBM-to-HBM row gathers (the rows by token in front of
    each sum back, the cotangent's rows) run over a piece's live chunks
    alone against the form it replaces (every gather over the whole static
    piece): the value, the rows' counts and ALL gradients equal bit for bit
    at any load, in both dtypes and both expert forms — only rows that no
    kernel reads and no sum counts stop being made."""
    tokens, k, held, total = 1024, 4, 4, 32
    assert piece_rows(tokens, k, held, total) == 1024
    assert chunk_rows(1024) == 128
    chosen = _landing(tokens, k, held, total, landed)
    got, want = _both_forms(chosen, total, _live_chunk_args(dtype, gated))
    _assert_same_bits(got, want)
    stats = np.asarray(got[0][1][1])
    assert tuple(stats[:3]) == (0.0, landed, float(landed > 1024))
    assert stats[5] == made


@pytest.mark.parametrize("landed, dtype, gated", [
    (700, jnp.float32, True), (1025, jnp.bfloat16, False)],
    ids=["swiglu-float32", "relu2-bfloat16-second-pieces"])
def test_live_chunks_under_two_expert_shards(eight_devices, landed, dtype,
                                             gated):
    """The same under ``ep`` = 2 on CPU devices: each shard's pieces (512
    rows of its two experts) have their own live counts, and the parts and
    the counts are summed."""
    tokens, k, held, total = 1024, 4, 4, 32
    chosen = _landing(tokens, k, held, total, landed)
    args = _live_chunk_args(dtype, gated)
    got, want = _both_forms(chosen, total, args, shards=2)
    _assert_same_bits(got, want)
    stats = np.asarray(got[0][1][1])
    on_shard = [int(((np.asarray(chosen) // 2) == s).sum()) for s in (0, 1)]
    assert sum(on_shard) == landed == stats[1]
    assert chunk_rows(512) == 128
    # a shard's pieces before its last are whole, the last one's live chunks
    assert stats[5] == sum(n // 512 * 512 + -(-(n % 512) // 128) * 128
                           for n in on_shard)


def test_nothing_reads_the_rows_behind_the_last_live_chunk():
    """The arrays the chunk loops write into start as NaN throughout (as
    the interpreter leaves a kernel's unwritten result; on the chip they
    hold whatever the memory did): the value and every gradient are the
    whole-piece form's all the same, so nothing downstream reads a dead
    row."""
    tokens, k, held, total = 1024, 4, 4, 32
    chosen = _landing(tokens, k, held, total, 300)
    seen = []

    def nan(shape, dtype, after, interpret):
        seen.append(shape)
        return jnp.full(shape, jnp.nan, dtype)

    got, want = _both_forms(chosen, total,
                            _live_chunk_args(jnp.bfloat16, True),
                            _unwritten=nan)
    _assert_same_bits(got, want)
    # the forward's weights and rows by token; the backward's weights by
    # row, the cotangent's rows and the rows' gradient by token
    assert sorted(seen) == [(1024,)] * 2 + [(1024, 16)] * 3


@pytest.mark.parametrize("tokens, k, held, total", [
    (16384, 8, 32, 256),    # Laguna's cell
    (16384, 1, 8, 17),      # ZAYA1's
    (16384, 8, 16, 256),    # JoyAI-LLM-Flash's
    (16384, 6, 8, 128),     # Nemotron 3 Nano's
    (16384, 8, 16, 64),     # Mellum 2's
], ids=["laguna", "zaya1", "joyai", "nemotron", "mellum2"])
def test_no_chunk_edge_near_the_expected_load(tokens, k, held, total):
    """A balanced layer must not flip between one chunk more and one less
    by the step: in the five cells no edge of a chunk lies within 3% of the
    expected load (half a piece), the chunks are whole row tiles, and nine
    of them hold the piece."""
    piece, expected = piece_rows(tokens, k, held, total), \
        tokens * k * held / total
    chunk = chunk_rows(piece)
    assert chunk % 128 == 0 and 8 * chunk < piece <= 9 * chunk
    nearest = min(abs(edge - expected)
                  for edge in range(0, piece + chunk, chunk))
    assert nearest > 0.03 * expected, (chunk, expected, nearest)
    # and a balanced load makes five chunks of nine
    assert moe.rows_made(int(expected), piece) == 5 * chunk


def _fixed_route(chosen):
    """A stand-in for ``route``: these choices, uniform weights."""
    def route(h, kernel, k, scaling, **_):
        logits = jnp.dot(h.astype(jnp.float32), kernel.astype(jnp.float32))
        return logits, chosen, jnp.full(chosen.shape, scaling / k)
    return route


@pytest.mark.parametrize("landed, made", [(0, 0), (385, 512),
                                          (1025, 1024 + 128)])
def test_row_fill_is_the_landed_rows_over_the_chunks_made(monkeypatch,
                                                          landed, made):
    """``moe_row_fill`` through the layer at three loads against the count
    by hand: 1,024 tokens on 4 of 32 experts, pieces of 1,024 rows in chunks
    of 128; 1 where nothing landed."""
    tokens, k, held, total = 1024, 4, 4, 32
    monkeypatch.setattr(moe, "route", _fixed_route(
        _landing(tokens, k, held, total, landed)))
    layer = MoeMlp(experts_total=total, experts_held=(0, held), d_ff=8,
                   shared_d_ff=0, k=k)
    x, = normal(1, (2, tokens // 2, 16))
    _, counted, _ = jax.jit(layer.apply)(
        jax.jit(layer.init)(jax.random.PRNGKey(2), x), x)
    named = dict(zip(COUNTERS, np.asarray(counted)))
    assert named["moe_rows_per_token"] == np.float32(landed / tokens)
    assert named["moe_row_fill"] == np.float32(landed / made if made else 1.0)
    assert named["moe_dropped"] == 0.0


@pytest.mark.parametrize("router, more", [
    (moe.ROUTERS[0], {}),
    (moe.ROUTERS[1], {"router_hidden": 8, "skip_choice": True, "k": 1}),
    (moe.ROUTERS[2], {})])
def test_row_fill_has_its_place_in_every_routers_counters(router, more):
    """The eighth of the counters whatever the router's form, in front of
    what a form adds; through a small layer it is the landed rows over the
    whole chunks they need."""
    names = moe.counters(more.get("skip_choice", False), router)
    assert names[:8] == COUNTERS and names[7] == "moe_row_fill"
    assert COUNTERS[6:] == ("moe_tile_fill", "moe_row_fill")
    layer = MoeMlp(**dict(dict(experts_total=16, experts_held=(0, 4), d_ff=8,
                               shared_d_ff=0, k=4, router=router), **more))
    x, = normal(3, (2, 128, 16))
    state = jnp.zeros((2, 128, 8)) if router == moe.ROUTERS[1] else None
    _, counted, _ = jax.jit(layer.apply)(
        jax.jit(layer.init)(jax.random.PRNGKey(4), x, state), x, state)
    named = dict(zip(names, np.asarray(counted)))
    assert len(named) == len(names) == len(counted)
    landed = round(float(named["moe_rows_per_token"]) * 256)
    piece = piece_rows(256, layer.k, 4, 16 + more.get("skip_choice", False))
    assert 0 < landed <= piece
    assert named["moe_row_fill"] == np.float32(
        landed / moe.rows_made(landed, piece))


def test_row_fill_reaches_the_loss_metrics_as_the_layers_mean():
    """``lm_bundle``'s metrics carry ``moe_row_fill`` beside the other
    counters: the mean over the expert layers, each the landed rows over
    its chunks."""
    from easydl_tpu.models.laguna import describe
    from easydl_tpu.models.registry import get_model

    kwargs = dict(size="test", seq_len=32, vocab=128, experts_held=(0, 4))
    bundle = get_model("laguna", **kwargs)
    rng = jax.random.PRNGKey(1)
    params = jax.jit(bundle.init_fn)(rng)
    batch = next(iter(bundle.make_data(4, seed=5)))
    _, metrics = jax.jit(bundle.loss_fn)(params, batch, rng)
    assert set(COUNTERS) <= set(metrics)
    fill = float(metrics["moe_row_fill"])
    assert 0.0 < fill <= 1.0
    # a piece at this size is one chunk: every layer's fill is its landed
    # rows over its piece, the mean of the rows a token over the piece's
    tokens, cfg = 4 * 32, describe(**kwargs).moe
    piece = piece_rows(tokens, cfg.k, 4, cfg.experts_total)
    assert chunk_rows(piece) == piece
    assert fill == pytest.approx(
        float(metrics["moe_rows_per_token"]) * tokens / piece, rel=1e-6)


@pytest.mark.parametrize("tokens, k, rows, landed", [
    (256, 4, 512, 300),    # two blocks of tokens, four chunks of rows
    (200, 4, 300, 300),    # neither whole blocks nor whole chunks; all live
    (24, 2, 40, 0),        # nothing landed: zeros are written
    (640, 8, 128, 128),    # more blocks than chunks: empty blocks
])
def test_rows_to_tokens_is_the_float32_scatter_add(tokens, k, rows, landed):
    """The kernel that sums a piece's rows into their tokens' rows against
    ``.at[].add`` in float32; what dead rows hold (here NaN) adds nothing."""
    from easydl_tpu.ops.moe import _to_tokens

    rng = np.random.default_rng(0)
    choice = rng.permutation(tokens * k)[:rows].astype(np.int32)
    live = np.arange(rows) < landed
    data = rng.standard_normal((rows, 16)).astype(np.float32)
    scale = rng.random(rows).astype(np.float32)
    # the rows by token: a landed choice's number sorts them, dead rows last
    by_token = np.argsort(np.where(live, choice, tokens * k), kind="stable")
    got = jax.jit(_to_tokens, static_argnums=(3, 4, 5))(
        jnp.where(live[:, None], data, jnp.nan), scale[by_token],
        (by_token.astype(np.int32), choice[by_token], jnp.int32(landed)),
        tokens, k, True)
    want = np.zeros((tokens, 16), np.float32)
    np.add.at(want, choice[live] // k, scale[live, None] * data[live])
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_sums_back_to_the_tokens_by_landed_choices():
    """``d_weights`` and ``dh`` come from the rows' side: a token with no
    landed choice gets exact zeros, a choice elsewhere a zero weight
    gradient, tokens with one and three landed choices the loop's."""
    held, total = 4, 32
    chosen = jnp.asarray([[9, 17, 30, 4],     # none of the four held
                          [9, 2, 30, 4],      # one
                          [3, 17, 0, 1]],     # three
                         jnp.int32)
    args = (*_tokens_and_weights(0, 3, 4), *_held_experts(held))
    _, stats, (d_h, d_weights, *_) = _value_stats_grads(chosen, total, args)
    assert tuple(stats) == (0.0, 4.0, 0.0, 1.0)
    _, (d_h_want, d_weights_want, *_) = _loop_value_and_grads(chosen, args)
    assert not np.asarray(d_h[0]).any()
    assert not np.asarray(d_weights)[np.asarray(chosen) >= held].any()
    np.testing.assert_allclose(d_h, d_h_want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(d_weights, d_weights_want, rtol=1e-5, atol=1e-4)


def test_moe_mlp_forward_and_grads():
    layer = MoeMlp(experts_total=8, experts_held=(0, 8), d_ff=32,
                   shared_d_ff=16, k=2, scaling=2.5)
    x, = normal(1, (2, 16, 8))
    params = jax.jit(layer.init)(jax.random.PRNGKey(2), x)

    def loss(params, x):
        y, counters, _ = layer.apply(params, x)
        return (y ** 2).mean(), counters

    (val, counters), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(params, x)
    assert np.isfinite(float(val))
    named = dict(zip(COUNTERS, np.asarray(counters)))
    assert named["moe_dropped"] == 0.0 and named["moe_rows_per_token"] == 2.0
    assert named["moe_load_max_over_mean"] >= 1.0
    assert 0.0 < named["moe_buffer_fill"] <= 1.0
    assert 0.0 < named["router_entropy"] <= np.log(8) + 1e-6
    grads = shd.unbox(grads)["params"]
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    # the router receives gradient (the weights depend on it), and so does
    # every expert that got a row
    assert np.abs(np.asarray(grads["router"])).sum() > 0
    assert np.abs(np.asarray(grads["w_down"])).sum() > 0
    assert np.abs(np.asarray(grads["shared_down"])).sum() > 0


# ------------------------------------------------------ the grouped products
def _groups_loop(x, w, sizes, transposed=False):
    """``x[a:b] @ w[g]`` group by group (float64 on the host); rows behind
    the last group stay NaN: nothing may read them."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    out = np.full((x.shape[0], w.shape[1 if transposed else 2]), np.nan)
    a = 0
    for g, n in enumerate(sizes):
        out[a:a + n] = x[a:a + n] @ (w[g].T if transposed else w[g])
        a += n
    return out


#: rows, the groups' sizes: with row tiles of 128 —
GROUPINGS = {
    "an_empty_group": (384, [100, 0, 150, 30]),
    "empty_first_and_last": (384, [0, 200, 100, 0]),
    "one_group_holds_every_row": (384, [0, 384, 0]),
    "a_group_over_several_tiles": (640, [60, 400, 90]),
    "sizes_off_the_tile_grid": (300, [1, 127, 129, 43]),
    "rows_off_the_tile_grid_all_live": (200, [50, 150]),
    "zero_landed_rows": (256, [0, 0, 0]),
    "groups_on_the_tile_grid": (512, [128, 256, 128]),
}


def _grouped_case(name, dtype, contract=16, cols=24):
    rows, sizes = GROUPINGS[name]
    rng = np.random.default_rng(len(name))
    live = np.arange(rows)[:, None] < sum(sizes)
    # NaN in every row behind the last group, of both row operands
    x = jnp.asarray(np.where(live, rng.standard_normal((rows, contract)),
                             np.nan), dtype)
    y = jnp.asarray(np.where(live, rng.standard_normal((rows, cols)), np.nan),
                    dtype)
    w = jnp.asarray(rng.standard_normal((len(sizes), contract, cols)), dtype)
    return x, y, w, sizes, jnp.asarray(sizes, jnp.int32)


def _weights_loop(x, y, sizes):
    """``x[a:b]^T @ y[a:b]`` group by group (float64 on the host)."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    ends = np.cumsum(sizes)
    return np.stack([x[a:b].T @ y[a:b] for a, b in zip(ends - sizes, ends)])


def _close(got, want, dtype, live=None):
    got, want = np.asarray(got, np.float64), np.asarray(want)
    if live is not None:
        got, want = got[:live], want[:live]
    assert np.isfinite(got).all()
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(np.abs(want).max(initial=0), 1))


# the grouped products under jit, interpreted: module-level, so that cases of
# equal shapes share a program (the groups' sizes are an argument)
@functools.partial(jax.jit, static_argnums=3)
def _rows(xs, ws, sizes, transposed):
    return grouped_rows(xs, ws, sizes, transposed, True)


@jax.jit
def _weights(x, y, sizes):
    return grouped_weights(x, y, sizes, True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(GROUPINGS))
def test_grouped_rows_is_a_loop_over_the_groups(name, dtype):
    """``[R, C] x [G, C, N]`` and ``[R, N] x [G, C, N]^T`` against the loop,
    in the operands' dtype from a float32 sum; a pair of operands is the
    sum of both products; what rows behind the last group hold (NaN) reaches
    no live row, and the visited tiles are the ones the groups touch."""
    x, y, w, sizes, s = _grouped_case(name, dtype)
    live = sum(sizes)
    out, visited = _rows([x], [w], s, False)
    assert out.shape == (x.shape[0], w.shape[2]) and out.dtype == dtype
    _close(out, _groups_loop(x, w, sizes), dtype, live)
    ends = np.cumsum(sizes)
    touched = sum(-(-b // 128) - a // 128
                  for a, b in zip(ends - sizes, ends) if b > a)
    assert int(visited) == 128 * touched
    out_t, _ = _rows([y], [w], s, True)
    assert out_t.shape == x.shape and out_t.dtype == dtype
    _close(out_t, _groups_loop(y, w, sizes, True), dtype, live)
    both, _ = _rows([y, y], [w, -0.5 * w], s, True)
    _close(both, 0.5 * _groups_loop(y, w, sizes, True), dtype, live)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(GROUPINGS))
def test_grouped_weights_is_a_loop_over_the_groups(name, dtype):
    """``x[rows of g]^T y[rows of g]`` for every group against the loop: an
    empty group gets zeros, NaN rows behind the last group are not summed."""
    x, y, _, sizes, s = _grouped_case(name, dtype)
    got = _weights(x, y, s)
    assert got.shape == (len(sizes), x.shape[1], y.shape[1])
    assert got.dtype == dtype
    _close(got, _weights_loop(x, y, sizes), dtype)
    for g, n in enumerate(sizes):
        if n == 0:
            assert not np.asarray(got[g], np.float32).any()


def test_grouped_products_in_blocks_of_columns(monkeypatch):
    """A weight block that holds 128 of 320 columns: three blocks, the last
    a partial one, each expert's rows passing under each."""
    from easydl_tpu.ops import moe

    monkeypatch.setattr(moe, "WEIGHT_BLOCK_BYTES", 128 * 16 * 4)
    x, y, w, sizes, s = _grouped_case("an_empty_group", jnp.float32, cols=320)
    assert moe.choose_tiles(384, 4, 16, 320, 4) == (128, 128)
    live = sum(sizes)
    out, _ = jax.jit(
        lambda x, w, s: moe.grouped_rows([x], [w], s, False, True))(x, w, s)
    _close(out, _groups_loop(x, w, sizes), jnp.float32, live)
    back, _ = jax.jit(lambda y, w, s: moe.grouped_rows(
        [y], [jnp.swapaxes(w, 1, 2)], s, False, True))(y, w, s)
    _close(back, _groups_loop(y, w, sizes, True), jnp.float32, live)
    got = jax.jit(lambda x, y, s: moe.grouped_weights(x, y, s, True))(x, y, s)
    _close(got, _weights_loop(x, y, sizes), jnp.float32)


@pytest.mark.parametrize("rows, groups, contract, cols, itemsize, want", [
    # ZAYA1's cell: a piece of 15,488 rows over 8 experts of 2048 x 2048 —
    # 964 rows a group: the least row tile, the weight block whole
    (15488, 8, 2048, 2048, 2, (128, 2048)),
    (15488, 8, 2048, 2048, 4, (128, 1024)),    # float32: half the columns
    # Laguna's: 32,768 rows over 32 experts, 2048 x 512 and 512 x 2048 —
    # a step of 128 rows is too little work, 512 rows a group allow 256
    (32768, 32, 2048, 512, 2, (256, 512)),
    (32768, 32, 512, 2048, 2, (256, 2048)),
    (131072, 256, 2048, 512, 2, (128, 512)),   # all 256 held: 256 rows each
    (65536, 8, 512, 512, 2, (512, 512)),       # small experts, many rows
    # Nemotron 3 Nano's: 12,288 rows over 8 experts, 2688 x 1856 and back —
    # 14.5 lane tiles, a block of 9.98 MB: two equal blocks of whole lane
    # tiles (the second holds 832 and 1,280), not 1,536 + 320 and 2,176 + 512
    (12288, 8, 2688, 1856, 2, (128, 1024)),
    (12288, 8, 1856, 2688, 2, (128, 1408)),
    # Mellum 2's: 65,536 rows over 16 experts, 2304 x 896 (seven lane tiles
    # of columns, the block whole) and back — 2,048 rows a group allow 256
    (65536, 16, 2304, 896, 2, (256, 896)),
    (65536, 16, 896, 2304, 2, (256, 2304)),
    # the tier-1 sizes: tiles of 128 rows or the rows themselves
    (1024, 4, 16, 8, 4, (128, 8)),
    (384, 4, 16, 24, 4, (128, 24)),
    (64, 8, 16, 8, 4, (64, 8)),
    (12, 4, 16, 8, 4, (12, 8)),
])
def test_tiles_follow_the_shapes(rows, groups, contract, cols, itemsize, want):
    from easydl_tpu.ops.moe import choose_tiles

    assert choose_tiles(rows, groups, contract, cols, itemsize) == want


def _routing(tokens, k, held, total, rows_of):
    """``chosen [tokens, k]``: ``rows_of[e]`` tokens choose the held expert
    ``e`` first, every other choice falls on an expert elsewhere."""
    chosen = held + (np.arange(tokens)[:, None] + np.arange(k)) % (total - held)
    first = np.repeat(np.arange(held), rows_of)
    chosen[:first.size, 0] = first
    return jnp.asarray(chosen, jnp.int32)


@pytest.mark.parametrize("name, tokens, k, held, total, d, f, rows_of, dtype", [
    # ZAYA1's test widths, one choice over 8 of 17: one piece of 256 rows
    ("zaya_test", 256, 1, 8, 17, 128, 64, [20, 0, 31, 9, 40, 1, 17, 12],
     jnp.float32),
    ("zaya_test_bf16", 256, 1, 8, 17, 128, 64, [20, 0, 31, 9, 40, 1, 17, 12],
     jnp.bfloat16),
    # Laguna's, two choices over 4 of 16: pieces of 256 of a bound of 512
    ("laguna_test", 256, 2, 4, 16, 64, 32, [100, 3, 0, 90], jnp.float32),
    ("laguna_test_second_piece", 256, 2, 4, 16, 64, 32, [0, 200, 56, 0],
     jnp.float32),
    ("laguna_test_bf16", 256, 2, 4, 16, 64, 32, [100, 3, 0, 90], jnp.bfloat16),
    ("every_row_on_one_expert", 256, 2, 4, 16, 64, 32, [0, 0, 256, 0],
     jnp.float32),
    ("nothing_lands", 256, 2, 4, 16, 64, 32, [0, 0, 0, 0], jnp.float32),
])
def test_the_experts_rule_is_the_loops_gradient(name, tokens, k, held, total,
                                                d, f, rows_of, dtype):
    """The value and all five gradients through the hand-written rule — the
    gate and up products made again, no down product, the rows' gradient as
    one call over a pair — against reverse mode through a float32 loop over
    the experts."""
    chosen = _routing(tokens, k, held, total, rows_of)
    landed = sum(rows_of)
    args32 = (*_tokens_and_weights(3, tokens, k, d),
              *(0.3 * np.asarray(w) for w in _held_experts(held, d, f)))
    args = tuple(jnp.asarray(np.asarray(a).astype(dtype)) if i != 1 else a
                 for i, a in enumerate(args32))
    value, stats, grads = _value_stats_grads(chosen, total, args)
    assert tuple(stats[:2]) == (0.0, landed)
    assert stats[2] == float(landed > piece_rows(tokens, k, held, total))
    want, grads_want = _loop_value_and_grads(
        chosen, [np.asarray(a, np.float32) for a in args])
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    # each gradient in its argument's dtype (the choices' weights' float32)
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    for got, looped in zip((value, *grads), (want, *grads_want)):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(
            got, looped, rtol=0,
            atol=tol * max(float(jnp.abs(looped).max()), 1.0))
    if not landed:
        assert not any(np.asarray(g, np.float32).any() for g in grads)


def test_tile_fill_counts_the_tiles_the_groups_touch():
    """256 tokens on two experts, 100 and 156: the first expert's rows lie
    in the first tile of 128, the second's in both — three tiles visited
    for 256 rows; and through the layer at its smallest, where one tile of
    64 rows holds every row and is visited once for each expert that got
    any."""
    from easydl_tpu.ops.moe import _routed_part

    chosen = jnp.asarray(np.repeat([0, 1], [100, 156])[:, None], jnp.int32)
    h, weights = _tokens_and_weights(0, 256, 1)
    _, stats = jax.jit(_routed_part, static_argnums=(4, 5))(
        h, chosen, weights, tuple(_held_experts(2)), 0, 2)
    # (the sixth: one piece of 256 rows in two chunks of 128, both live)
    assert tuple(np.asarray(stats)) == (0.0, 256.0, 0.0, 156.0, 3 * 128.0,
                                        256.0)

    layer = MoeMlp(experts_total=8, experts_held=(0, 8), d_ff=32,
                   shared_d_ff=16, k=2, scaling=2.5)
    x, = normal(1, (2, 16, 8))
    (_, counters, _), sown = jax.jit(lambda params, x: layer.apply(
        params, x, mutable=["intermediates"]))(
            jax.jit(layer.init)(jax.random.PRNGKey(2), x), x)
    named = dict(zip(COUNTERS, np.asarray(counters)))
    experts = np.unique(np.asarray(sown["intermediates"]["chosen"][0])).size
    assert COUNTERS[-2] == "moe_tile_fill"
    assert named["moe_tile_fill"] == np.float32(64 / (64 * experts))


# ------------------------------------------------ ungated relu2 experts
def _relu2_loop(h, chosen, weights, w_up, w_down):
    """The routed result of ungated experts ``relu(h W_up)^2 W_down`` as a
    loop over the experts held, every token through every expert."""
    y = jnp.zeros_like(h)
    for e in range(w_up.shape[0]):
        weight = jnp.where(chosen == e, weights, 0.0).sum(-1)
        y = y + weight[:, None] * (
            jnp.square(jax.nn.relu(h @ w_up[e])) @ w_down[e])
    return y


@jax.jit
def _relu2_loop_value_and_grads(chosen, args):
    return jax.value_and_grad(
        lambda *a: _weighed(_relu2_loop(a[0], chosen, *a[1:])),
        argnums=(0, 1, 2, 3))(*args)


@functools.partial(jax.jit, static_argnums=1)
def _relu2_routed_value_and_grads(chosen, total, args):
    def loss(h, weights, w_up, w_down):
        y, stats = routed_experts(h, chosen, weights, None, w_up, w_down, 0,
                                  total)
        return _weighed(y), stats

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(*args)


@pytest.mark.parametrize("name, rows_of, dtype", [
    # 256 tokens, two choices over 4 of 16 experts of a RAGGED width (24: no
    # multiple of 16, as Nemotron 3 Nano's 1,856 is none of 128): pieces of
    # 256 of a bound of 512
    ("ragged", [100, 3, 0, 90], jnp.float32),
    ("ragged_second_piece", [0, 200, 56, 0], jnp.float32),
    ("ragged_bf16", [100, 3, 0, 90], jnp.bfloat16),
    ("every_row_on_one_expert", [0, 0, 256, 0], jnp.float32),
    ("nothing_lands", [0, 0, 0, 0], jnp.float32),
])
def test_the_relu2_rule_is_the_loops_gradient(name, rows_of, dtype):
    """The value and all four gradients of ungated experts through the
    hand-written rule — the up product made again, ``d_u = d_act * 2
    relu(u)``, ONE product back to the rows — against reverse mode through a
    float32 loop over the experts; no choice without a row."""
    tokens, k, held, total, d, f = 256, 2, 4, 16, 64, 24
    chosen = _routing(tokens, k, held, total, rows_of)
    landed = sum(rows_of)
    _, w_up, w_down = _held_experts(held, d, f)
    args32 = (*_tokens_and_weights(5, tokens, k, d),
              0.3 * np.asarray(w_up), 0.3 * np.asarray(w_down))
    args = tuple(jnp.asarray(np.asarray(a).astype(dtype)) if i != 1 else a
                 for i, a in enumerate(args32))
    (value, stats), grads = _relu2_routed_value_and_grads(chosen, total, args)
    stats = np.asarray(stats)
    assert tuple(stats[:2]) == (0.0, landed)          # dropped, landed
    assert stats[2] == float(landed > piece_rows(tokens, k, held, total))
    want, grads_want = _relu2_loop_value_and_grads(
        chosen, [np.asarray(a, np.float32) for a in args])
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    for got, looped in zip((value, *grads), (want, *grads_want)):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(
            got, looped, rtol=0,
            atol=tol * max(float(jnp.abs(looped).max()), 1.0))


def test_a_relu2_layer_has_two_leaves_an_expert():
    """``expert_form="relu2"``: ``w_up`` and ``w_down`` of the routed
    experts, ``shared_up`` and ``shared_down`` of the shared one, no gate
    leaf; the layer's result is the loop's plus the shared expert's, nothing
    dropped; an unknown form is refused."""
    layer = MoeMlp(experts_total=8, experts_held=(0, 8), d_ff=24,
                   shared_d_ff=40, k=2, scaling=2.5, selection_bias=True,
                   expert_form="relu2", down_zero_sums=True)
    x, = normal(1, (2, 16, 8))
    params = jax.jit(layer.init)(jax.random.PRNGKey(2), x)
    leaves = params["params"]
    assert sorted(leaves) == ["router", "router_bias", "shared_down",
                              "shared_up", "w_down", "w_up"]
    # ``down_zero_sums``: the down maps start with zero column sums (a
    # positive activation's mean then adds nothing token-independent), the
    # up maps do not; without the word the down maps are plain normal
    plain = shd.unbox(leaves)
    for name in ("w_down", "shared_down"):
        sums = np.abs(np.asarray(plain[name]).sum(-2))
        assert sums.max() < 1e-6, name
        assert np.asarray(plain[name]).std() == pytest.approx(0.02, rel=0.2)
    assert np.abs(np.asarray(plain["w_up"]).sum(-2)).max() > 1e-3
    normal_down = shd.unbox(jax.jit(layer.clone(down_zero_sums=False).init)(
        jax.random.PRNGKey(2), x)["params"])["w_down"]
    assert np.abs(np.asarray(normal_down).sum(-2)).max() > 1e-3
    (y, counters, _), sown = jax.jit(lambda params, x: layer.apply(
        params, x, mutable=["intermediates"]))(params, x)
    named = dict(zip(COUNTERS, np.asarray(counters)))
    assert named["moe_dropped"] == 0.0 and named["moe_rows_per_token"] == 2.0

    @jax.jit
    def by_hand(p, x, chosen, logits):
        h = x.reshape(-1, x.shape[-1])
        scores = jnp.take_along_axis(jax.nn.sigmoid(logits), chosen, -1)
        weights = 2.5 * scores / scores.sum(-1, keepdims=True)
        shared = jnp.square(jax.nn.relu(h @ p["shared_up"])) \
            @ p["shared_down"]
        return shared + _relu2_loop(h, chosen, weights, p["w_up"],
                                    p["w_down"])

    kept = sown["intermediates"]
    want = by_hand(shd.unbox(leaves), x, kept["chosen"][0],
                   kept["router_logits"][0])
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape),
                               np.asarray(want), atol=1e-5)
    with pytest.raises(ValueError, match="expert form"):
        MoeMlp(experts_total=8, experts_held=(0, 8), d_ff=24, shared_d_ff=0,
               k=2, expert_form="geglu").init(jax.random.PRNGKey(0), x)


# ------------------------------------------- the linear softmax router
SOFTMAX = "linear-softmax-renormalised"


@pytest.mark.parametrize("scaling", [1.0, 2.5])
def test_softmax_routing_invariants(scaling):
    """``route(..., softmax=True)``: the ``k`` largest of the softmax over
    ALL experts, each weight its probability over the chosen ones' sum times
    the scaling — the softmax over the chosen logits alone."""
    tokens, d, total, k = 64, 16, 16, 4
    h, kernel = normal(3, (tokens, d), (d, total))
    logits, chosen, weights = jax.jit(
        functools.partial(route, softmax=True), static_argnums=(2, 3))(
            np.asarray(h).astype(jnp.bfloat16), kernel, k, scaling)
    assert logits.dtype == jnp.float32 and logits.shape == (tokens, total)
    logits, chosen, weights = (np.asarray(a) for a in (logits, chosen,
                                                       weights))
    assert all(len(set(row)) == k for row in chosen)
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(-logits, -1)[:, :k], -1))
    np.testing.assert_allclose(weights.sum(-1), scaling, rtol=1e-6)
    picked = np.take_along_axis(logits, chosen, -1).astype(np.float64)
    over_chosen = np.exp(picked - picked.max(-1, keepdims=True))
    np.testing.assert_allclose(
        weights, scaling * over_chosen / over_chosen.sum(-1, keepdims=True),
        rtol=1e-5)


def test_softmax_routing_gradient_passes_the_renormalisation():
    """The weights' gradient by the router is the gradient of the softmax
    over the chosen logits (the chosen sets held fixed): what the other
    experts' logits do to the whole softmax cancels in the quotient."""
    tokens, d, total, k = 32, 16, 16, 4
    h, kernel, ct = normal(4, (tokens, d), (d, total), (tokens, k))
    chosen = jax.jit(functools.partial(route, softmax=True),
                     static_argnums=(2, 3))(h, kernel, k, 1.0)[1]

    def mine(kernel):
        return jnp.sum(route(h, kernel, k, 1.0, softmax=True)[2] * ct)

    def by_hand(kernel):
        picked = jnp.take_along_axis(
            jnp.dot(h, kernel, precision="highest"), chosen, -1)
        return jnp.sum(jax.nn.softmax(picked, -1) * ct)

    got, want = jax.jit(jax.grad(mine))(kernel), jax.jit(
        jax.grad(by_hand))(kernel)
    assert np.abs(np.asarray(want)).max() > 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def _softmax_layer(held=(0, 16), **more):
    return MoeMlp(experts_total=16, experts_held=held, d_ff=8, shared_d_ff=0,
                  k=4, router=SOFTMAX, **more)


def test_softmax_router_takes_no_selection_bias():
    x, = normal(1, (2, 16, 16))
    with pytest.raises(ValueError, match="selection_bias=True"):
        _softmax_layer(selection_bias=True).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="skip_choice=True"):
        _softmax_layer(skip_choice=True).init(jax.random.PRNGKey(0), x)


def test_softmax_layer_counts_the_chosen_mass():
    """One counter more where the router is the linear softmax:
    ``router_chosen_mass``, k / experts at zero logits (and the entropy
    log(experts)); between that and 1 at seeded weights; the other forms'
    vectors are as long as they were."""
    from easydl_tpu.ops.moe import counters

    names = counters(router=SOFTMAX)
    assert names == COUNTERS + ("router_chosen_mass",)
    assert counters() == COUNTERS and counters(True) == COUNTERS + (
        "moe_skipped",)
    layer = _softmax_layer()
    x, = normal(1, (2, 16, 16))
    params = jax.jit(layer.init)(jax.random.PRNGKey(2), x)
    assert sorted(params["params"]) == ["router", "w_down", "w_gate", "w_up"]
    apply = jax.jit(layer.apply)
    named = dict(zip(names, np.asarray(apply(params, x)[1])))
    assert len(named) == len(names)
    assert 4 / 16 < named["router_chosen_mass"] < 1.0
    assert named["moe_dropped"] == 0.0 and named["moe_rows_per_token"] == 4.0
    flat = jax.tree.map(lambda a: a, shd.unbox(params))
    flat["params"]["router"] = jnp.zeros_like(flat["params"]["router"])
    named = dict(zip(names, np.asarray(apply(flat, x)[1])))
    assert named["router_chosen_mass"] == pytest.approx(4 / 16, rel=1e-6)
    assert named["router_entropy"] == pytest.approx(np.log(16), rel=1e-6)


def test_four_shares_of_a_softmax_layer_sum_to_the_whole():
    """Nothing shared: the parts the four shares ``[0, 4) .. [12, 16)`` of
    one layer give (each holding its slice of the same expert weights, the
    router whole) add up to the layer that holds all sixteen, and so do the
    rows that landed; the whole is the loop over the experts under the
    softmax's weights."""
    whole = _softmax_layer()
    x, = normal(5, (2, 16, 16))
    params = shd.unbox(jax.jit(whole.init)(jax.random.PRNGKey(3), x))["params"]
    (y, counted, _), sown = jax.jit(lambda p, x: whole.apply(
        {"params": p}, x, mutable=["intermediates"]))(params, x)
    names = ("moe_rows_per_token",)
    total, rows = jnp.zeros_like(y), 0.0
    for lo in range(0, 16, 4):
        share = {k: (v if k == "router" else v[lo:lo + 4])
                 for k, v in params.items()}
        part, c, _ = jax.jit(_softmax_layer((lo, lo + 4)).apply)(
            {"params": share}, x)
        total, rows = total + part, rows + float(c[1])
    np.testing.assert_allclose(np.asarray(total), np.asarray(y), atol=1e-6)
    assert rows == pytest.approx(float(counted[1])) and rows == 4.0
    kept = sown["intermediates"]
    chosen, logits = kept["chosen"][0], kept["router_logits"][0]
    weights = jax.nn.softmax(jnp.take_along_axis(logits, chosen, -1), -1)
    want = jax.jit(_loop_over_experts)(
        x.reshape(-1, 16), chosen, weights, params["w_gate"], params["w_up"],
        params["w_down"])
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape),
                               np.asarray(want), atol=1e-5)

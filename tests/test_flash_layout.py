"""The flash kernels on the model's own layout, ``[batch, seq,
heads·head_dim]`` in blocks of whole 128-lane tiles: even and odd head
counts, rectangles of few block pairs a head and of many, grouped queries
whose shared key/value heads the kernels read by index, and nothing but
reshapes around the ``pallas_call``s. Interpreted on the CPU, ONE jitted
program a side and case (``conftest.out_and_grads``) on inputs drawn on the
host."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import normal, out_and_grads
from test_flash_backward import cos_weighed, flash_and_reference

from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops.attention import multihead_attention
from easydl_tpu.ops.flash_attention import (
    Band,
    BlockDiffusion,
    choose_blocks,
    flash_attention,
)


def _grads_within(got, want, tol):
    """dq, dk, dv finite and within ``tol`` of the largest entry of the
    reference's."""
    for g, w, name in zip(got, want, "qkv"):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), f"d{name}"
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), f"d{name}"


def _flash_vs_reference(q, k, v, *, causal, block_q, block_k, atol, rtol,
                        grad_tol):
    """Forward and the three gradients of the interpreted kernels against
    the XLA reference on the same inputs."""
    (out, got), (want, g_want) = flash_and_reference(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=atol, rtol=rtol)
    _grads_within(got, g_want, grad_tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,d", [
    (2, 64), (4, 64), (3, 64), (25, 64), (1, 64), (2, 128), (3, 128), (5, 32)],
    ids=["pair", "two-pairs", "odd-3", "odd-25", "lone-64", "two-of-128",
         "odd-of-128", "five-of-32"])
def test_the_models_layout_even_and_odd_head_counts(heads, d, dtype):
    """``[batch, seq, heads·head_dim]`` blocks of whole 128-lane tiles: two
    heads of 64 (or four of 32, one of 128) side by side in a grid cell; an
    odd count leaves the last cell half outside the array, and what lies
    there reaches no live head's output or gradient."""
    q, k, v = normal(11, *[(2, 64, heads, d)] * 3, dtype=dtype)
    tight = dtype == "float32"
    _flash_vs_reference(q, k, v, causal=True, block_q=32, block_k=32,
                        atol=2e-5 if tight else 2e-2,
                        rtol=2e-5 if tight else 2e-2,
                        grad_tol=5e-4 if tight else 3e-2)


@pytest.mark.parametrize("heads", [2, 3], ids=["even", "odd"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s_q,s_k,block_q,block_k", [
    (64, 64, 32, 32), (64, 128, 32, 64), (128, 64, 64, 32),
    (256, 256, 32, 32), (128, 256, 16, 64), (1280, 1280, 256, 256),
    (1536, 1024, 256, 256)],
    ids=["square", "sq<sk", "sq>sk", "64-pairs-square", "64-pairs-sq<sk",
         "in-tiles", "in-tiles-sq>sk"])
def test_the_models_layout_rectangular_few_pairs_and_many(
        s_q, s_k, block_q, block_k, causal, heads):
    """Heads of 64 two to a lane block at four block pairs a head and at 64
    (until PR 60 the two sides of a rule: unrolled, looped), square and with
    an offset either way (dead rows where s_q > s_k); at blocks of 256 the
    kernels walk a pair in tiles of 128."""
    q, k, v = normal(12, (1, s_q, heads, 64), *[(1, s_k, heads, 64)] * 2)
    _flash_vs_reference(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, atol=2e-5, rtol=2e-5, grad_tol=5e-4)


def _grouped(name, heads, kv_heads, d=64, rows=64, block=None, batch=2,
             dtype="float32", **call):
    return pytest.param(heads, kv_heads, d, rows, block, batch, dtype, call,
                        name.endswith("band"), id=name)


#: the four of PR 30 at heads of 64 under the rule's own blocks (one short
#: pair: a cell widened to four heads where they divide the count, so the
#: whole ratio is repeated), then heads of 128 — one to a cell, read by
#: index — at four block pairs a head and at 25, one short pair a head (two
#: heads a cell: a shared head repeated six-fold, the ratio a cell's heads do
#: not divide), two heads of 64 a cell under a ratio of four (the
#: hybrid's: repeated two-fold), the band path and the block mask
GROUPED = [
    _grouped("4-over-2", 4, 2), _grouped("6-over-3", 6, 3),
    _grouped("6-over-1", 6, 1), _grouped("3-over-3", 3, 3),
    _grouped("128-8-over-2-4-pairs", 8, 2, 128, 64, 32, 1),
    _grouped("128-6-over-1-4-pairs", 6, 1, 128, 64, 32, 1),
    _grouped("128-6-over-1-one-pair", 6, 1, 128, 32, 32, 1),
    _grouped("128-8-over-2-25-pairs", 8, 2, 128, 160, 32, 1),
    _grouped("128-6-over-1-25-pairs", 6, 1, 128, 160, 32, 1),
    _grouped("128-8-over-2-25-pairs-bf16", 8, 2, 128, 160, 32, 1, "bfloat16"),
    _grouped("64-8-over-2-25-pairs", 8, 2, 64, 160, 32, 1),
    _grouped("128-8-over-2-band", 8, 2, 128, 192, 16, 1, window=9),
    _grouped("128-6-over-1-band", 6, 1, 128, 192, 16, 1, window=16),
    _grouped("128-8-over-2-window-looped", 8, 2, 128, 256, 16, 1, window=17),
    _grouped("128-8-over-2-blockmask-16-pairs", 8, 2, 128, 128, 32, 1,
             mask=BlockDiffusion(4, 64)),
    _grouped("128-6-over-1-blockmask-64-pairs", 6, 1, 128, 256, 32, 1,
             mask=BlockDiffusion(4, 128)),
]


@pytest.mark.parametrize(
    "heads,kv_heads,d,rows,block,batch,dtype,call,band", GROUPED)
def test_grouped_queries_reach_the_kernels_by_index(
        monkeypatch, heads, kv_heads, d, rows, block, batch, dtype, call,
        band):
    """``multihead_attention`` hands the kernels k and v at the key/value
    heads and query head ``h`` reads head ``h // ratio`` by its index: the
    result is, bit for bit, that of the kernels on k and v REPEATED to the
    query's heads in front of them (the parent's program), dq likewise, and
    dk, dv — at k's and v's own shapes — the repeat's transpose of theirs;
    all four stand by the reference path's."""
    kernels = functools.partial(flash_attention, interpret=True,
                                block_q=block, block_k=block)
    monkeypatch.setattr(attention_module, "flash_attention", kernels)
    q, k, v = normal(13, (batch, rows, heads, d),
                     *[(batch, rows, kv_heads, d)] * 2, dtype=dtype)
    call = dict(causal="mask" not in call, **call)
    took = choose_blocks(rows, rows, call["causal"], block, block,
                         call.get("window"), call.get("mask"))
    assert isinstance(took[0], Band) == band

    def repeated(q, k, v):
        return kernels(q, *(jnp.repeat(x, heads // kv_heads, axis=2)
                            for x in (k, v)), **call)

    out, got = out_and_grads(functools.partial(
        multihead_attention, impl="flash", **call), cos_weighed)(q, k, v)
    same, g_same = out_and_grads(repeated, cos_weighed)(q, k, v)
    want, g_want = out_and_grads(functools.partial(
        multihead_attention, impl="reference", **call), cos_weighed)(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(same))
    for g, w, name in zip(got, g_same, "qkv"):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"d{name}")
    tight = dtype == "float32"
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if tight else 2e-2, rtol=2e-5 if tight else 2e-2)
    _grads_within(got, g_want, 5e-4 if tight else 3e-2)


def test_head_counts_that_do_not_divide_are_refused():
    """Five query heads over two key/value heads, and k's count beside
    another of v's: the ValueError is the kernels' own."""
    q, k, v = normal(15, (1, 64, 5, 64), *[(1, 64, 2, 64)] * 2)
    with pytest.raises(ValueError, match="5 query heads over 2 / 2 key"):
        flash_attention(q, k, v, causal=True, interpret=True)
    with pytest.raises(ValueError, match="4 query heads over 2 / 4 key"):
        flash_attention(q[:, :, :4], k, jnp.repeat(v, 2, axis=2),
                        causal=True, interpret=True)
    with pytest.raises(ValueError, match="flash_attention refuses it"):
        multihead_attention(q, k, v, causal=True, impl="flash")


def _equations_outside_kernels(fn, *args):
    """Every equation ``fn(*args)`` traces to, at any depth, except what
    runs inside a ``pallas_call``."""
    seen = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            seen.append(eqn)
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for v in value if isinstance(value, (tuple, list)) else (value,):
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


def _primitives_outside_kernels(fn, *args):
    """Their primitives' names."""
    return [eqn.primitive.name
            for eqn in _equations_outside_kernels(fn, *args)]


@pytest.mark.parametrize("heads", [4, 3], ids=["even", "odd"])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_no_transpose_stands_outside_the_kernels(what, heads):
    """The kernels take q, k, v, O, dO and give O, dq, dk, dv in the model's
    own layout: around the two ``pallas_call``s ``flash_attention`` and
    its gradient hold reshapes only — no ``transpose``, and no product of
    whole arrays either (``delta`` is formed in the kernels; the one
    ``reduce_sum`` is this test's loss)."""
    q, k, v = normal(14, *[(2, 64, heads, 64)] * 3, dtype="bfloat16")
    flash = functools.partial(flash_attention, causal=True, block_q=32,
                              block_k=32, interpret=True)
    fn = flash if what == "forward" else jax.grad(
        lambda q, k, v: flash(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    names = _primitives_outside_kernels(fn, q, k, v)
    assert names.count("pallas_call") == (1 if what == "forward" else 2)
    assert "transpose" not in names, names
    assert not {"dot_general", "mul"} & set(names), names


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("rows,call,kernels", [
    (1024, dict(causal=True), 1), (4096, dict(causal=True), 1),
    (4096, dict(causal=True, window=512), 2),
    (4096, dict(mask=BlockDiffusion(4, 2048)), 1)],
    ids=["one-pair", "64-pairs", "band", "blockmask"])
def test_no_repeat_stands_in_front_of_the_kernels(what, rows, call, kernels):
    """``multihead_attention(impl="flash")`` at heads of 128, eight query
    heads over two key/value heads: every ``pallas_call`` takes k and v
    ``kv_heads·d`` = 256 lanes wide beside q's 1,024, and nothing outside the
    kernels repeats, joins or gathers (``broadcast_in_dim``, ``concatenate``,
    ``gather``). The gradient holds exactly two ``reduce_sum``s, dk's and
    dv's over a group's four query heads, which leave the kernels a query
    head: ``[batch, rows, 2, 4, 128]`` over axis 3."""
    heads, kv_heads, d = 8, 2, 128
    q, k, v, g = normal(16, (1, rows, heads, d),
                        *[(1, rows, kv_heads, d)] * 2, (1, rows, heads, d),
                        dtype="bfloat16")
    attend = functools.partial(multihead_attention, impl="flash", **call)
    fn = attend if what == "forward" else (
        lambda q, k, v: jax.vjp(attend, q, k, v)[1](g))
    eqns = _equations_outside_kernels(fn, q, k, v)
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == (1 if what == "forward" else 1 + kernels)
    for e in calls:
        widths = sorted(x.aval.shape[-1] for x in e.invars
                        if x.aval.dtype == jnp.bfloat16)
        # k and v (the band kernels take each a second time: the
        # neighbour's block), then q, and in the backward O, dO
        assert set(widths) == {kv_heads * d, heads * d}, (e.params["name"],
                                                          widths)
        assert widths.count(kv_heads * d) in (2, 4), (e.params["name"], widths)
    names = [e.primitive.name for e in eqns]
    assert not {"broadcast_in_dim", "concatenate", "gather",
                "transpose"} & set(names), names
    sums = [e for e in eqns if e.primitive.name == "reduce_sum"]
    assert [(e.invars[0].aval.shape, tuple(e.params["axes"]))
            for e in sums] == ([] if what == "forward" else [
                ((1, rows, kv_heads, heads // kv_heads, d), (3,))] * 2)

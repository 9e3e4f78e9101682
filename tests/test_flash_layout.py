"""The flash kernels on the model's own layout, ``[batch, seq,
heads·head_dim]`` in blocks of whole 128-lane tiles: even and odd head
counts, rectangles on both sides of ``_UNROLL_PAIRS``, grouped queries
repeated in front of the kernels' view, and nothing but reshapes around the
``pallas_call``s. Interpreted on the CPU, ONE jitted program a side and case
(``conftest.out_and_grads``) on inputs drawn on the host."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import normal, out_and_grads
from test_flash_backward import (assert_grads_close, cos_weighed,
                                 flash_and_reference)

from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops.attention import multihead_attention
from easydl_tpu.ops.flash_attention import flash_attention


def _flash_vs_reference(q, k, v, *, causal, block_q, block_k, atol, rtol,
                        grad_tol):
    """Forward and the three gradients of the interpreted kernels against
    the XLA reference on the same inputs."""
    (out, got), (want, g_want) = flash_and_reference(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=atol, rtol=rtol)
    for g, w, name in zip(got, g_want, "qkv"):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), f"d{name}"
        assert np.abs(g - w).max() <= grad_tol * np.abs(w).max(), f"d{name}"


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
    ids=["square", "sq<sk", "sq>sk", "looped-square", "looped-sq<sk",
         "looped-in-tiles", "looped-in-tiles-sq>sk"])
def test_the_models_layout_rectangular_unrolled_and_looped(
        s_q, s_k, block_q, block_k, causal, heads):
    """Heads of 64 two to a lane block on both sides of ``_UNROLL_PAIRS``,
    square and with an offset either way (dead rows where s_q > s_k); at
    blocks of 256 the looped forward walks a pair in tiles of 128."""
    q, k, v = normal(12, (1, s_q, heads, 64), *[(1, s_k, heads, 64)] * 2)
    _flash_vs_reference(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, atol=2e-5, rtol=2e-5, grad_tol=5e-4)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (6, 3), (6, 1), (3, 3)],
                         ids=["4-over-2", "6-over-3", "6-over-1", "3-over-3"])
def test_grouped_queries_reach_the_kernels_repeated(monkeypatch, heads, kv_heads):
    """``multihead_attention`` repeats the shared key/value heads on the
    heads axis in front of the kernels' view; the repeat's transpose sums
    their gradients."""
    monkeypatch.setattr(attention_module, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    q, k, v = normal(13, (2, 64, heads, 64), *[(2, 64, kv_heads, 64)] * 2)
    flash = functools.partial(multihead_attention, causal=True, impl="flash")
    ref = functools.partial(multihead_attention, causal=True, impl="reference")
    out, got = out_and_grads(flash, cos_weighed)(q, k, v)
    want, g_want = out_and_grads(ref, cos_weighed)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    assert_grads_close(got, g_want, atol=5e-4, rtol=5e-4)


def _primitives_outside_kernels(fn, *args):
    """Names of every primitive ``fn(*args)`` traces to, at any depth,
    except what runs inside a ``pallas_call``."""
    seen = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            seen.append(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for v in value if isinstance(value, (tuple, list)) else (value,):
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


@pytest.mark.parametrize("heads", [4, 3], ids=["even", "odd"])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_no_transpose_stands_outside_the_kernels(what, heads):
    """The kernels take q, k, v, O, dO and give O, dq, dk, dv in the model's
    own layout: around the three ``pallas_call``s ``flash_attention`` and
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
    assert names.count("pallas_call") == (1 if what == "forward" else 3)
    assert "transpose" not in names, names
    assert not {"dot_general", "mul"} & set(names), names

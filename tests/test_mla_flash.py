"""The flash kernels at two head sizes (latent attention: scores 192 deep,
values 128 wide), interpreted on the CPU against the plain product form:
forward and all three gradients, causal, few block pairs a head and many, block edges
that fall inside the sequence, a rectangle, a head count the cell's tile
does not divide; how many heads a cell takes; the calls' names; the public
path through ``multihead_attention``; and q's rotation by the kernel on rows
of 192-lane heads beside the one key vector's in ``jax.numpy``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import normal, out_and_grads

from easydl_tpu.ops import attention, flash_attention as fa
from easydl_tpu.ops.rope import apply_rope, rope_rows, rope_tables


def _plain(q, k, v, scale):
    """Whole score matrices, one head at a time, float64 on the host."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    s_q, s_k = q.shape[1], k.shape[1]
    seen = np.arange(s_k)[None, :] <= np.arange(s_q)[:, None] + (s_k - s_q)
    out = np.zeros(q.shape[:3] + v.shape[3:])
    for b in range(q.shape[0]):
        for h in range(q.shape[2]):
            scores = q[b, :, h] @ k[b, :, h].T * scale
            scores = np.where(seen, scores, -np.inf)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            out[b, :, h] = p / p.sum(-1, keepdims=True) @ v[b, :, h]
    return out


def _operands(s_q, s_k, heads, d, dv, seed=0):
    """q, k, v and a cotangent of the result's shape."""
    return normal(seed, (2, s_q, heads, d), (2, s_k, heads, d),
                  (2, s_k, heads, dv), (2, s_q, heads, dv))


CASES = {
    # s_q, s_k, heads, d, dv, block_q, block_k
    "16-pairs-24-16": (256, 256, 4, 24, 16, 64, 64),
    "4-pairs-192-128": (256, 256, 2, 192, 128, 128, 128),
    "looped-192-128": (640, 640, 2, 192, 128, 128, 128),   # 25 block pairs
    "looped-uneven-blocks": (768, 768, 2, 24, 16, 128, 64),
    # the forward's pair in tiles of 128 x 128, two heads a cell: eight
    # tiles, four behind (and the backward reads that forward's lse)
    "looped-192-128-in-tiles": (1280, 1280, 2, 192, 128, 256, 256),
    "looped-rectangle-in-tiles": (768, 1536, 2, 192, 128, 256, 256),
    "rectangle": (128, 384, 2, 192, 128, 64, 128),
    "three-heads-of-a-tile-of-two": (128, 128, 3, 192, 128, 64, 64),
    "value-wider-than-score": (256, 256, 2, 64, 128, 64, 64),
}


@pytest.mark.parametrize("case", CASES)
def test_kernels_at_two_head_sizes_against_the_plain_form(case):
    s_q, s_k, heads, d, dv, block_q, block_k = CASES[case]
    q, k, v, g = _operands(s_q, s_k, heads, d, dv)
    scale = d ** -0.5

    def weighed(out):
        return jnp.sum(out * g)

    # the kernels' forward and gradients: one program; the reference's: one
    out, got = out_and_grads(functools.partial(
        fa.flash_attention, causal=True, scale=scale, block_q=block_q,
        block_k=block_k, interpret=True), weighed)(q, k, v)
    assert out.shape == (2, s_q, heads, dv)
    np.testing.assert_allclose(np.asarray(out), _plain(q, k, v, scale),
                               atol=2e-5)
    _, want = out_and_grads(functools.partial(
        attention._reference_attention, causal=True, scale=scale),
        weighed)(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   err_msg=f"d{name}")


def test_bf16_operands_keep_the_two_roundings():
    q, k, v, _ = _operands(256, 256, 2, 192, 128)
    q, k, v = (np.asarray(x).astype(jnp.bfloat16) for x in (q, k, v))
    out = jax.jit(functools.partial(
        fa.flash_attention, causal=True, interpret=True))(q, k, v)
    want = jax.jit(functools.partial(
        attention._reference_attention, causal=True, scale=192 ** -0.5))(
            q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_a_cell_takes_the_heads_that_fill_whole_tiles_of_both_sizes():
    cell = fa._cell_heads
    # 192 is one and a half lane tiles: two heads to a 384-lane block of q
    # and k, which are two whole tiles of v, O and dO
    assert cell(32, 192, 128) == 2
    assert cell(32, 192, 192) == 2
    assert cell(32, 128, 192) == 2
    # equal sizes, named or not: what one size gave
    for heads, d in ((16, 64), (25, 64), (8, 128), (4, 32)):
        assert cell(heads, d, d) == cell(heads, d)
    assert cell(16, 64) == 2 and cell(8, 128) == 1
    # fewer heads than the tile: all of them
    assert cell(4, 24, 16) == 4


def test_the_calls_are_named_by_what_they_compute():
    def names(d, dv, window=None, s=128):
        x = jnp.zeros((1, s, 2, d))
        v = jnp.zeros((1, s, 2, dv))
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(
                q, k, v, causal=True, interpret=True, window=window,
                block_q=min(s, 128), block_k=min(s, 128))),
            (0, 1, 2)))(x, x, v))
        return {name for kind in ("flash", "swa", "mla", "diff")
                for name in (f"{kind}_fwd", f"{kind}_bwd_dq",
                             f"{kind}_bwd_dkv", f"{kind}_bwd")
                if f"name={name} " in text or f"name={name}\n" in text}

    assert names(192, 128) == {"mla_fwd", "mla_bwd"}
    assert names(128, 128) == {"flash_fwd", "flash_bwd"}
    assert names(64, 64, window=32) == {"swa_fwd", "swa_bwd_dq",
                                        "swa_bwd_dkv"}
    # 25 block pairs a head as one: the backward is one call
    assert names(192, 128, s=640) == {"mla_fwd", "mla_bwd"}
    assert names(128, 128, s=640) == {"flash_fwd", "flash_bwd"}
    assert names(64, 64, window=192, s=640) == {"swa_fwd", "swa_bwd"}
    with pytest.raises(NotImplementedError, match="window"):
        names(192, 128, window=32)
    # values the WIDER (differential attention's pair: PR 53), which a
    # window takes too: the band path keeps its names
    assert names(64, 128) == {"diff_fwd", "diff_bwd"}
    assert names(64, 128, s=640) == {"diff_fwd", "diff_bwd"}
    assert names(64, 128, window=32) == {"swa_fwd", "swa_bwd_dq",
                                         "swa_bwd_dkv"}


def test_the_public_path_takes_a_value_size_of_its_own(monkeypatch):
    """``multihead_attention`` with ``impl="flash"``: the kernels'
    (interpreted) result at v's head size, equal to the reference path's."""
    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    q, k, v, _ = _operands(256, 256, 4, 24, 16, seed=3)
    kernels, reference = (jax.jit(functools.partial(
        attention.multihead_attention, causal=True, impl=impl))(q, k, v)
        for impl in ("flash", "reference"))
    assert kernels.shape == reference.shape == (2, 256, 4, 16)
    np.testing.assert_allclose(np.asarray(kernels), np.asarray(reference),
                               atol=2e-5)


def test_q_is_rotated_on_its_rows_and_the_key_vector_once(monkeypatch):
    """``rotate_heads``: with ``impl="flash"`` the kernel on ``[batch, seq,
    heads x 192]`` rows (units of two heads, 384 lanes), else ``jax.numpy``;
    both the written-out rotation of a head's LAST 64 lanes in pairs ``(2i,
    2i + 1)``, with its gradient."""
    from easydl_tpu.ops import rope

    seq, heads, d, rot = 64, 4, 192, 64
    tables = rope_tables(seq, d, 32e6, rot, interleaved=True, last=True)
    x, key = normal(0, (2, seq, heads, d), (2, seq, 1, rot))
    angle = np.arange(seq)[:, None] * 32e6 ** (-np.arange(0, rot, 2) / rot)
    want = np.asarray(x, np.float64)
    a, b = want[..., d - rot::2].copy(), want[..., d - rot + 1::2].copy()
    cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    want[..., d - rot::2] = a * cos - b * sin
    want[..., d - rot + 1::2] = b * cos + a * sin
    plain = jax.jit(lambda x: attention.rotate_heads(
        x, tables, rotary_dim=rot, interleaved=True, impl="reference"))(x)
    np.testing.assert_allclose(np.asarray(plain), want, atol=2e-5)
    monkeypatch.setattr(attention, "rope_rows", functools.partial(
        rope.rope_rows, interpret=True))

    def kernel(x):
        return attention.rotate_heads(x, tables, rotary_dim=rot,
                                      interleaved=True, impl="flash")

    assert "rope_fwd" in str(jax.make_jaxpr(kernel)(x))
    def cubed(out):
        return jnp.sum(out ** 3)

    rotated, (g_kernel,) = out_and_grads(kernel, cubed)(x)
    np.testing.assert_allclose(np.asarray(rotated), want, atol=2e-5)
    _, (g_plain,) = out_and_grads(lambda x: apply_rope(
        x, *tables, rot=rot, interleaved=True), cubed)(x)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_plain),
                               atol=1e-4)
    # the key's one vector: a head of its own, all of it rotated, the
    # tables the q tables' last lanes
    lone = jax.jit(lambda key: apply_rope(
        key, *(t[:, -rot:] for t in tables), interleaved=True))(key)
    whole = jax.jit(lambda key: apply_rope(jnp.concatenate(
        [jnp.zeros((2, seq, 1, d - rot)), key], -1), *tables, rot=rot,
        interleaved=True))(key)
    np.testing.assert_allclose(np.asarray(lone),
                               np.asarray(whole[..., d - rot:]), atol=1e-6)
    # three heads of 192 do not fill whole units: jax.numpy, told apart
    assert not rope.tiles_lanes(192, 3, True) and rope.tiles_lanes(192, 4,
                                                                  True)
    with pytest.raises(ValueError, match="128-lane tiles"):
        rope_rows(x[:, :, :3].reshape(2, seq, -1), *tables, head_dim=d,
                  interpret=True, interleaved=True)

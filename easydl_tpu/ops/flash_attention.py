"""Pallas TPU flash attention (forward + backward).

Memory-efficient attention: the [S, S] score matrix never hits HBM — each
(batch·head, q-block) grid cell streams K/V through VMEM with an online
softmax (running max + normaliser), so HBM traffic is O(S·d) instead of
O(S²). This is the hot op the reference would have written in CUDA
(SURVEY.md §2.1 item 5); on TPU it is a Pallas kernel tiled for the MXU
(block sizes multiples of 128 lanes).

Backward follows the standard flash decomposition: save per-row logsumexp
``lse`` from the forward; recompute P = exp(qkᵀ·scale − lse) blockwise; a
dq kernel loops K-blocks, a dk/dv kernel loops Q-blocks; the rowwise
``delta = Σ dO∘O`` term is a cheap XLA einsum outside the kernels.

Public shapes: [batch, seq, heads, head_dim] (the models' layout); kernels
run on a [batch·heads, seq, head_dim] view.

The kernels are compiled by Mosaic, which needs a TPU. ``interpret=True``
runs them in the Pallas interpreter instead — something only a test passes,
to check numerics against the XLA reference path without hardware.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "flash_attention")

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _pick_block(s: int, target: int) -> Optional[int]:
    """Largest block <= target that divides s, preferring multiples of 128
    (MXU/lane tiling). None when s can't be tiled — caller falls back to the
    reference path."""
    b = min(target, s)
    if s % b == 0:
        return b
    if s % 128 == 0:
        b -= b % 128
        while b >= 128:
            if s % b == 0:
                return b
            b -= 128
    return None


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, causal: bool, scale: float, offset: int):
    # q_ref: [1, block_q, d]; k_ref/v_ref: [1, S_k, d]
    # offset = s_k - s_q: causal masking is bottom-right aligned (matches the
    # reference path's tril(k=s_k-s_q) — row r attends cols <= r + offset).
    block_q, d = q_ref.shape[-2:]
    s_k = k_ref.shape[-2]
    q_idx = pl.program_id(1)
    q = q_ref[...].reshape(block_q, d).astype(jnp.float32) * scale

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    n_k = s_k // block_k
    if causal:
        # Only K-blocks at or before this Q-block's last row contribute.
        n_k_live = jnp.clip(
            ((q_idx + 1) * block_q + offset + block_k - 1) // block_k, 0, n_k
        )
    else:
        n_k_live = n_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        if causal:
            rows = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_k_live, body, (m0, l0, acc0))
    # Rows that saw no unmasked key (bottom-right-aligned causal with
    # s_q > s_k leaves the first s_q - s_k rows empty) still have m at the
    # NEG_INF sentinel: their p would be exp(0)=1, silently averaging V.
    # Define such rows as zero output, and poison their lse to +|NEG_INF| so
    # the backward's exp(s - lse) underflows to exactly 0 (no grad leak).
    dead = m <= NEG_INF * 0.5
    l = jnp.maximum(l, 1e-30)
    o = jnp.where(dead, 0.0, acc / l)
    o_ref[...] = o.reshape(o_ref.shape).astype(o_ref.dtype)
    # lse is [1, block_q, 1]: trailing dims (block_q, 1) satisfy the TPU
    # (8, 128)-or-full tiling rule, unlike a bare (1, block_q) block.
    lse = jnp.where(dead, -NEG_INF, m + jnp.log(l))
    lse_ref[...] = lse.reshape(lse_ref.shape)


def _fwd(q, k, v, *, causal: bool, scale: float, block_q: int, block_k: int, interpret: bool):
    # q,k,v: [BH, S, d]
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    assert s_q % block_q == 0 and s_k % block_k == 0, (s_q, s_k, block_q, block_k)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale, offset=s_k - s_q
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, s_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, s_k, d), lambda b, qi: (b, 0, 0)),
            pl.BlockSpec((1, s_k, d), lambda b, qi: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, block_k: int, causal: bool, scale: float, offset: int,
):
    block_q, d = q_ref.shape[-2:]
    s_k = k_ref.shape[-2]
    q_idx = pl.program_id(1)
    q = q_ref[...].reshape(block_q, d).astype(jnp.float32) * scale
    do = do_ref[...].reshape(block_q, d).astype(jnp.float32)
    lse = lse_ref[...].reshape(block_q, 1)
    delta = delta_ref[...].reshape(block_q, 1)

    n_k = s_k // block_k
    if causal:
        n_k_live = jnp.clip(
            ((q_idx + 1) * block_q + offset + block_k - 1) // block_k, 0, n_k
        )
    else:
        n_k_live = n_k

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            rows = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(0, n_k_live, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[...] = (dq * scale).reshape(dq_ref.shape).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, block_q: int, causal: bool, scale: float, offset: int,
):
    block_k, d = dk_ref.shape[-2:]
    s_q = q_ref.shape[-2]
    k_idx = pl.program_id(1)
    k = k_ref[...].reshape(block_k, d).astype(jnp.float32)
    v = v_ref[...].reshape(block_k, d).astype(jnp.float32)

    n_q = s_q // block_q
    # Q-blocks whose rows all satisfy row + offset < col never attend (causal).
    if causal:
        first_q = jnp.clip((k_idx * block_k - offset) // block_q, 0, n_q)
    else:
        first_q = 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :].reshape(block_q, 1)
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :].reshape(block_q, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_k]
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first_q, n_q, body, (dk0, dv0))
    # dk accumulated q·scale contributions; gradient w.r.t. k needs no extra
    # scale beyond the one already folded into q.
    dk_ref[...] = dk.reshape(dk_ref.shape).astype(dk_ref.dtype)
    dv_ref[...] = dv.reshape(dv_ref.shape).astype(dv_ref.dtype)


def _bwd(
    q, k, v, out, lse, do, *, causal: bool, scale: float,
    block_q: int, block_k: int, interpret: bool,
):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    delta = jnp.einsum(
        "bsd,bsd->bs", do.astype(jnp.float32), out.astype(jnp.float32)
    )[..., None]

    offset = s_k - s_q
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_k=block_k, causal=causal, scale=scale, offset=offset
        ),
        grid=(bh, s_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, s_k, d), lambda b, qi: (b, 0, 0)),
            pl.BlockSpec((1, s_k, d), lambda b, qi: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, causal=causal, scale=scale, offset=offset
        ),
        grid=(bh, s_k // block_k),
        in_specs=[
            pl.BlockSpec((1, s_q, d), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, s_q, d), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, s_q, 1), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, s_q, 1), lambda b, ki: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    out, _ = _fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    dq, dk, dv = _bwd(
        q, k, v, out, lse, g, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over [batch, seq, heads, head_dim] tensors.

    Drops to the XLA reference path when the kernel can't tile the sequence
    lengths (no block divisor) or a segment mask is requested; each such
    drop is logged once with its reason."""
    b, s, h, d = q.shape
    s_k = k.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bq = _pick_block(s, block_q)
    bk = _pick_block(s_k, block_k)
    if segment_ids is not None or bq is None or bk is None:
        from easydl_tpu.ops.attention import _reference_attention

        why = ("segment mask requested" if segment_ids is not None else
               f"lengths q={s} k={s_k} have no block divisor <= "
               f"{block_q}/{block_k}")
        log_once(log, f"flash attention: XLA reference path, not the "
                      f"kernel: {why}")
        return _reference_attention(
            q, k, v, causal=causal, scale=scale, segment_ids=segment_ids,
        )
    block_q, block_k = bq, bk
    device = jax.devices()[0]
    how = "INTERPRETED" if interpret else "compiled"
    log_once(log, f"flash attention: {how} Pallas kernel on "
                  f"{device.platform} ({device.device_kind})")
    # [B, S, H, d] -> [B*H, S, d]
    def to_bh(x, sl):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, sl, d)

    out = _flash(
        to_bh(q, s), to_bh(k, s_k), to_bh(v, s_k),
        causal, scale, block_q, block_k, interpret,
    )
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)

"""Pallas TPU flash attention (forward + backward).

Memory-efficient attention: the [S, S] score matrix never hits HBM — each
grid cell streams K/V blocks through VMEM with an online softmax (running
max + normaliser), so HBM traffic is O(S·d) instead of O(S²). This is the
hot op the reference would have written in CUDA (SURVEY.md §2.1 item 5); on
TPU it is a Pallas kernel tiled for the MXU (block sizes multiples of 128
lanes).

Backward follows the standard flash decomposition: save per-row logsumexp
``lse`` from the forward; recompute P = exp(qkᵀ·scale − lse) blockwise; a
dq kernel loops K-blocks, a dk/dv kernel loops Q-blocks; the rowwise
``delta = Σ dO∘O`` term is a cheap XLA einsum outside the kernels.

What follows the input and what is float32, always. Every matmul takes its
operands in the dtype q, k, v and dO arrive in (bf16 in, bf16 to the MXU;
float32 in, float32 operands as before) and accumulates in float32
(``preferred_element_type``). The probabilities are rounded to ``v.dtype``
before ``P V`` and ``Pᵀ dO``, ``dS`` to ``q.dtype`` before ``dS K`` and
``dSᵀ Q`` — the two roundings ``ops/attention._reference_attention`` makes.
Float32 regardless of the input: the scores as they leave the MXU, the
running max and normaliser, ``exp``, ``lse``, ``delta``, ``dp − delta`` and
the output / dq / dk / dv accumulators. The softmax scale costs no second
rounding: it is folded into the block a loop keeps (q, or k in the dk/dv
kernel) when that is exact — float32, or a power of two as 1/8 is at
head_dim 64 — and multiplies the float32 scores otherwise.

All three kernels hold a score tile transposed, ``Sᵀ = K Qᵀ`` as
``[block_k, block_q]``: keys along sublanes, queries along lanes. The
per-query statistics (max, normaliser, ``lse``, ``delta``) are then rows of
``block_q`` lanes instead of ``[block_q, 1]`` columns that fill one lane in
128, reductions over keys are elementwise across vregs, ``Pᵀ dO`` and
``dSᵀ Q`` are plain products, and the forward and dq accumulate ``Oᵀ = Vᵀ
Pᵀ`` / ``dQᵀ = Kᵀ dSᵀ`` as ``[head_dim, block_q]`` (transposed once, when a
Q-block is finished; the ``[block_k, head_dim]`` blocks of V and K are
transposed in the kernel, on the otherwise idle XLU). For that the backward's
wrapper hands its kernels ``lse`` / ``delta`` as rows by Q-block: operands'
layouts, not results'.

Causal masking is bottom-right aligned (``offset = s_k − s_q``: query row
r sees key columns ≤ r + offset, as the reference's ``tril(k=s_k−s_q)``).
Each Q-block (K-block in dk/dv) walks its live block pairs in two loops:
pairs wholly below the diagonal take no mask, pairs the diagonal crosses are
masked. When the whole problem is at most ``_UNROLL_PAIRS`` block pairs, one
grid cell takes a whole batch·head (several where a head is a pair or two,
``_cell_heads``): every block index is then a Python number, dead pairs are
never emitted and both loops unroll into straight-line code that the compiler
schedules across pairs. Longer sequences take one Q-block (K-block) per grid
cell and loop at run time.

Public shapes: [batch, seq, heads, head_dim] (the models' layout); kernels
run on a [batch·heads, seq, head_dim] view.

The kernels are compiled by Mosaic, which needs a TPU. ``interpret=True``
runs them in the Pallas interpreter instead — something only a test passes,
to check numerics against the XLA reference path without hardware.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "flash_attention")

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: a problem of at most this many block pairs is one grid cell per
#: batch·head, unrolled (1024 x 1024 causal: 3 live pairs in blocks of 512,
#: 10 in blocks of 256).
_UNROLL_PAIRS = 16

#: block pairs an unrolled grid cell is filled up to with further
#: batch·heads. Every unrolled pair costs a cached program about 0.1 s of
#: set-up each time it is traced and loaded (PERF.md section 6, PR 24: four
#: heads a cell at 1024 x 1024 gave +1.2% tokens/s for +3.8 s), so only cells
#: smaller than this are filled.
_CELL_PAIRS = 4

#: what one grid cell's blocks may take of VMEM, one buffer each (Pallas
#: keeps two; float32 [4, 1024, 64] blocks of q, k, v, o did not fit).
_CELL_BYTES = 6 << 20

#: the largest block a kernel takes when the caller names none (the sweep
#: in :func:`choose_blocks`)
MAX_BLOCK = 512

#: (block_q, block_k) of the forward, the dq and the dk/dv kernel.
Blocks = Tuple[Tuple[int, int], Tuple[int, int], Tuple[int, int]]

#: dot_general dimension numbers: ``A Bᵀ`` (both contract their last
#: dimension) and the plain ``A B``.
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _pick_block(s: int, target: int) -> Optional[int]:
    """Largest block <= target that divides s, preferring multiples of 128
    (MXU/lane tiling). None when s can't be tiled."""
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


def choose_blocks(s_q: int, s_k: int, causal: bool,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None) -> Optional[Blocks]:
    """Block sizes per kernel from what the call can see; a caller's
    ``block_q`` / ``block_k`` hold for all three. None when a length has no
    block divisor: the kernels cannot tile it, and whoever chooses the
    attention path (``ops/attention.py``) asks here before calling them.

    Swept on a v5e over {128, 256, 512, 1024}² at [128, 1024, 64] and
    [100, 1024, 64] bf16 causal (PERF.md section 6, PR 24): 512 x 512 is
    fastest for the forward and dq; dk/dv, with four products a pair, gains
    more from wasting less of the causal triangle (5/8 of the matrix computed
    at 256², 3/4 at 512²) than it loses to more pairs — as long as the pairs
    still unroll. Without a triangle the smaller blocks have nothing to win."""
    def pick(target):
        return (_pick_block(s_q, block_q or target),
                _pick_block(s_k, block_k or target))

    big, small = pick(MAX_BLOCK), pick(MAX_BLOCK // 2)
    if None in big:
        return None
    dkv = big
    if causal and None not in small and _unrolled(
            s_q // small[0], s_k // small[1]):
        dkv = small
    return big, big, dkv


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fold_scale(x, scale: float):
    """``(x·scale, 1)`` where that is exact in x's dtype — float32, or a
    power-of-two scale — else ``(x, scale)``: the scale then multiplies the
    float32 scores, so no operand is rounded twice."""
    if x.dtype == jnp.float32 or math.frexp(scale)[0] == 0.5:
        return x * scale, 1.0
    return x, scale


def _clip(x, lo: int, hi: int):
    if isinstance(x, int):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _loop(lo, hi, body, carry, *, unroll: bool):
    """``fori_loop``, or the same iterations as straight-line code when the
    bounds are Python numbers and the caller wants them unrolled."""
    if not unroll:
        return jax.lax.fori_loop(lo, hi, body, carry)
    for i in range(lo, hi):
        carry = body(i, carry)
    return carry


def _block_start(i, block: int):
    return i * block if isinstance(i, int) else pl.multiple_of(i * block, block)


def _scores_t(k, q, s_scale: float, bound):
    """``Sᵀ = K Qᵀ`` of one block pair, float32 ``[block_k, block_q]``;
    causally masked when ``bound`` (= q_start + offset − k_start) is given:
    key j is seen by query i iff j − i <= bound."""
    st = _dot(k, q, _NT)
    if s_scale != 1.0:
        st = st * s_scale
    if bound is not None:
        keys = jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        queries = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st = jnp.where(keys - queries <= bound, st, NEG_INF)
    return st


def _over_k_blocks(body, carry, q_start, *, block_q: int, block_k: int, n_k: int,
                   offset: int, causal: bool, unroll: bool):
    """``body(kb, carry, masked=)`` over the K-blocks the Q-block at
    ``q_start`` sees: [0, n_full) lie wholly below the diagonal (every row
    sees every column) and take no mask, [n_full, n_live) are crossed by it,
    the rest are seen by no row."""
    if not causal:
        return _loop(0, n_k, functools.partial(body, masked=False), carry, unroll=unroll)
    n_full = _clip((q_start + offset + 1) // block_k, 0, n_k)
    n_live = _clip((q_start + block_q + offset + block_k - 1) // block_k, 0, n_k)
    carry = _loop(0, n_full, functools.partial(body, masked=False), carry, unroll=unroll)
    return _loop(n_full, n_live, functools.partial(body, masked=True), carry, unroll=unroll)


def _unrolled(n_q: int, n_k: int) -> bool:
    """Whether a problem of ``n_q x n_k`` block pairs is one grid cell per
    batch·head, walked in straight-line code (else one block per cell and
    loops at run time)."""
    return n_q * n_k <= _UNROLL_PAIRS


def _cell_heads(bh: int, pairs: int, unroll: bool, rows: int, like) -> int:
    """Batch·heads one grid cell takes. One, unless a head is so few block
    pairs (short sequences: one pair at 128 or 512) that a cell of it is
    mostly waiting — then as many as make ``_CELL_PAIRS`` pairs a cell and
    keep its operands and results (``rows`` rows a head of ``like``'s width
    and dtype, lane-padded as VMEM holds them) within ``_CELL_BYTES``:
    independent chains of products for the compiler to interleave."""
    if not unroll:
        return 1
    head_bytes = rows * -(-like.shape[-1] // 128) * 128 * like.dtype.itemsize
    most = max(1, min(_CELL_PAIRS // pairs, _CELL_BYTES // head_bytes))
    return max(g for g in range(1, most + 1) if bh % g == 0)


def _rows(x, block: int):
    """Per-query statistics, [BH, S] or [BH, S, 1], as one row of ``block``
    lanes per Q-block: [BH, S // block, 1, block]."""
    bh, s = x.shape[:2]
    return x.reshape(bh, s // block, 1, block)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref,
    *, block_q: int, block_k: int, causal: bool, scale: float, offset: int, unroll: bool,
):
    # q_ref, o_ref: [cell heads, cell rows, d]; lse_ref: [.., cell rows, 1];
    # k_ref, v_ref: [cell heads, S_k, d]
    heads, cell_rows, d = q_ref.shape
    n_k = k_ref.shape[-2] // block_k
    cell_start = 0 if unroll else pl.program_id(1) * cell_rows
    for g, j in itertools.product(range(heads), range(cell_rows // block_q)):
        rows = slice(j * block_q, (j + 1) * block_q)
        q_start = cell_start + j * block_q
        q, s_scale = _fold_scale(q_ref[g, rows, :], scale)

        def body(kb, carry, *, masked: bool):
            m, l, acc = carry  # [1, block_q], [1, block_q], [d, block_q]
            k_start = _block_start(kb, block_k)
            k = k_ref[g, pl.ds(k_start, block_k), :]
            vt = v_ref[g, pl.ds(k_start, block_k), :].T  # [d, block_k]
            st = _scores_t(k, q, s_scale,
                           q_start + offset - k_start if masked else None)
            m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            correction = jnp.exp(m - m_new)
            l_new = l * correction + jnp.sum(pt, axis=0, keepdims=True)
            acc_new = acc * correction + _dot(vt, pt.astype(vt.dtype), _NN)
            return m_new, l_new, acc_new

        carry = (
            jnp.full((1, block_q), NEG_INF, jnp.float32),
            jnp.zeros((1, block_q), jnp.float32),
            jnp.zeros((d, block_q), jnp.float32),
        )
        m, l, acc = _over_k_blocks(
            body, carry, q_start, block_q=block_q, block_k=block_k, n_k=n_k,
            offset=offset, causal=causal, unroll=unroll)
        # Rows that saw no unmasked key (bottom-right-aligned causal with
        # s_q > s_k leaves the first s_q - s_k rows empty) still have m at
        # the NEG_INF sentinel: their p would be exp(0)=1, silently averaging
        # V. Define such rows as zero output, and poison their lse to
        # +|NEG_INF| so the backward's exp(s - lse) underflows to exactly 0
        # (no grad leak).
        dead = m <= NEG_INF * 0.5
        l = jnp.maximum(l, 1e-30)
        o_t = jnp.where(dead, 0.0, acc / l)
        lse = jnp.where(dead, -NEG_INF, m + jnp.log(l))
        o_ref[g, rows, :] = o_t.T.astype(o_ref.dtype)
        # lse leaves as a [block_q, 1] column (the result's shape): a row of
        # 8 equal sublanes transposed, of which one lane is kept.
        lse_ref[g, rows, :] = jnp.broadcast_to(lse, (8, block_q)).T[:, :1]


def _fwd(q, k, v, *, causal: bool, scale: float, block_q: int, block_k: int, interpret: bool):
    # q,k,v: [BH, S, d]
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    assert s_q % block_q == 0 and s_k % block_k == 0, (s_q, s_k, block_q, block_k)
    n_q, n_k = s_q // block_q, s_k // block_k
    unroll = _unrolled(n_q, n_k)
    cell_rows = s_q if unroll else block_q
    # q, o and the lse column (float32, one lane in 128: as wide as two
    # bf16 [s_q, 128] blocks), k, v
    heads = _cell_heads(bh, n_q * n_k, unroll, 4 * s_q + 2 * s_k, q)
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, causal=causal, scale=scale,
        offset=s_k - s_q, unroll=unroll,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh // heads, s_q // cell_rows),
        in_specs=[
            pl.BlockSpec((heads, cell_rows, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((heads, s_k, d), lambda b, qi: (b, 0, 0)),
            pl.BlockSpec((heads, s_k, d), lambda b, qi: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((heads, cell_rows, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((heads, cell_rows, 1), lambda b, qi: (b, qi, 0)),
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
    *, block_q: int, block_k: int, causal: bool, scale: float, offset: int, unroll: bool,
):
    # lse_ref, delta_ref: [cell heads, Q-blocks of this cell, 1, block_q]
    heads, cell_rows, d = q_ref.shape
    n_k = k_ref.shape[-2] // block_k
    cell_start = 0 if unroll else pl.program_id(1) * cell_rows
    for g, j in itertools.product(range(heads), range(cell_rows // block_q)):
        rows = slice(j * block_q, (j + 1) * block_q)
        q_start = cell_start + j * block_q
        q, s_scale = _fold_scale(q_ref[g, rows, :], scale)
        do = do_ref[g, rows, :]
        lse = lse_ref[g, j]
        delta = delta_ref[g, j]

        def body(kb, dq_t, *, masked: bool):
            k_start = _block_start(kb, block_k)
            k = k_ref[g, pl.ds(k_start, block_k), :]
            v = v_ref[g, pl.ds(k_start, block_k), :]
            st = _scores_t(k, q, s_scale,
                           q_start + offset - k_start if masked else None)
            pt = jnp.exp(st - lse)
            dst = pt * (_dot(v, do, _NT) - delta)
            return dq_t + _dot(k.T, dst.astype(k.dtype), _NN)

        dq_t = _over_k_blocks(
            body, jnp.zeros((d, block_q), jnp.float32), q_start, block_q=block_q,
            block_k=block_k, n_k=n_k, offset=offset, causal=causal, unroll=unroll)
        dq_ref[g, rows, :] = (dq_t * scale).T.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, block_q: int, block_k: int, causal: bool, scale: float, offset: int, unroll: bool,
):
    # lse_ref, delta_ref: [cell heads, n_q, 1, block_q]
    heads, cell_rows, d = dk_ref.shape
    n_q = q_ref.shape[-2] // block_q
    cell_start = 0 if unroll else pl.program_id(1) * cell_rows
    for g, j in itertools.product(range(heads), range(cell_rows // block_k)):
        rows = slice(j * block_k, (j + 1) * block_k)
        k_start = cell_start + j * block_k
        k, s_scale = _fold_scale(k_ref[g, rows, :], scale)
        v = v_ref[g, rows, :]

        def body(qb, carry, *, masked: bool):
            dk, dv = carry
            q_start = _block_start(qb, block_q)
            q = q_ref[g, pl.ds(q_start, block_q), :]
            do = do_ref[g, pl.ds(q_start, block_q), :]
            st = _scores_t(k, q, s_scale,
                           q_start + offset - k_start if masked else None)
            pt = jnp.exp(st - lse_ref[g, qb])
            dv_new = dv + _dot(pt.astype(do.dtype), do, _NN)
            dst = pt * (_dot(v, do, _NT) - delta_ref[g, qb])
            dk_new = dk + _dot(dst.astype(q.dtype), q, _NN)
            return dk_new, dv_new

        carry = (
            jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32),
        )
        first_full = 0
        if causal:
            # Q-blocks before first_live see none of this K-block; from
            # first_full on every row sees all of it.
            first_live = _clip((k_start - offset) // block_q, 0, n_q)
            first_full = _clip(
                (k_start + block_k - 1 - offset + block_q - 1) // block_q, 0, n_q)
            carry = _loop(first_live, first_full, functools.partial(body, masked=True),
                          carry, unroll=unroll)
        dk, dv = _loop(first_full, n_q, functools.partial(body, masked=False), carry, unroll=unroll)
        # q entered the products unscaled (the scale sat on k or the scores).
        dk_ref[g, rows, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[g, rows, :] = dv.astype(dv_ref.dtype)


def _bwd(
    q, k, v, out, lse, do, *, causal: bool, scale: float,
    dq_blocks: Tuple[int, int], dkv_blocks: Tuple[int, int], interpret: bool,
):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    delta = jnp.einsum(
        "bsd,bsd->bs", do.astype(jnp.float32), out.astype(jnp.float32)
    )
    static = dict(causal=causal, scale=scale, offset=s_k - s_q)

    block_q, block_k = dq_blocks
    n_q, n_k = s_q // block_q, s_k // block_k
    unroll = _unrolled(n_q, n_k)
    heads = _cell_heads(bh, n_q * n_k, unroll, 3 * s_q + 2 * s_k, q)  # q do dq k v
    cell_q = n_q if unroll else 1  # Q-blocks a grid cell takes

    def whole(s, heads):
        return pl.BlockSpec((heads, s, d), lambda b, i: (b, 0, 0))

    mine = pl.BlockSpec((heads, cell_q * block_q, d), lambda b, qi: (b, qi, 0))
    mine_rows = pl.BlockSpec((heads, cell_q, 1, block_q), lambda b, qi: (b, qi, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          unroll=unroll, **static),
        grid=(bh // heads, n_q // cell_q),
        in_specs=[
            mine, whole(s_k, heads), whole(s_k, heads), mine, mine_rows, mine_rows,
        ],
        out_specs=mine,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, _rows(lse, block_q), _rows(delta, block_q))

    block_q, block_k = dkv_blocks
    n_q, n_k = s_q // block_q, s_k // block_k
    unroll = _unrolled(n_q, n_k)
    heads = _cell_heads(bh, n_q * n_k, unroll, 2 * s_q + 4 * s_k, q)  # q do k v dk dv
    cell_k = n_k if unroll else 1  # K-blocks a grid cell takes
    mine = pl.BlockSpec((heads, cell_k * block_k, d), lambda b, ki: (b, ki, 0))
    all_rows = pl.BlockSpec((heads, n_q, 1, block_q), lambda b, ki: (b, 0, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          unroll=unroll, **static),
        grid=(bh // heads, n_k // cell_k),
        in_specs=[whole(s_q, heads), mine, mine, whole(s_q, heads), all_rows, all_rows],
        out_specs=[mine, mine],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, _rows(lse, block_q), _rows(delta, block_q))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, blocks: Blocks, interpret):
    return _flash_fwd(q, k, v, causal, scale, blocks, interpret)[0]


def _flash_fwd(q, k, v, causal, scale, blocks: Blocks, interpret):
    out, lse = _fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=blocks[0][0], block_k=blocks[0][1], interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, blocks: Blocks, interpret, res, g):
    q, k, v, out, lse = res
    return _bwd(
        q, k, v, out, lse, g, causal=causal, scale=scale,
        dq_blocks=blocks[1], dkv_blocks=blocks[2], interpret=interpret,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over [batch, seq, heads, head_dim] tensors: the
    kernels' result, or ValueError where they cannot tile the lengths
    (:func:`choose_blocks` is the question; this module holds no other
    path).

    ``block_q`` / ``block_k``, when passed, hold for all three kernels; left
    out, each kernel's are chosen from what the call shows."""
    b, s, h, d = q.shape
    s_k = k.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    blocks = choose_blocks(s, s_k, causal, block_q, block_k)
    if blocks is None:
        raise ValueError(
            f"flash attention: lengths q={s} k={s_k} have no block divisor "
            f"<= {block_q or MAX_BLOCK}/{block_k or MAX_BLOCK}")
    device = jax.devices()[0]
    how = "INTERPRETED" if interpret else "compiled"
    chosen = ", ".join(
        f"{name} {bq}/{bk} "
        + ("unrolled" if _unrolled(s // bq, s_k // bk) else "looped")
        for name, (bq, bk) in zip(("fwd", "dq", "dkv"), blocks))
    log_once(log, f"flash attention: {how} Pallas kernel on "
                  f"{device.platform} ({device.device_kind}), "
                  f"{jnp.dtype(q.dtype).name} operands to the MXU, blocks "
                  f"q/k {chosen}, over lengths {s}/{s_k}, head_dim {d}")
    # [B, S, H, d] -> [B*H, S, d]
    def to_bh(x, sl):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, sl, d)

    out = _flash(
        to_bh(q, s), to_bh(k, s_k), to_bh(v, s_k),
        causal, scale, blocks, interpret,
    )
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)

"""Kimi Delta Attention's recurrence (KDA; the Kimi Linear report,
arXiv:2510.26692, equation 1): a delta rule whose state forgets at a rate of
its own in every key CHANNEL. Per head, with state ``S [d_k, d_v]`` from zero:

    S'  = Diag(alpha_t) S_{t-1}                  alpha_t = exp(g_t) in (0, 1]
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T         o_t = S_t^T q_t

The update SUBTRACTS what the state already holds along ``k_t`` (``I - beta k
k^T``), which ``ops/ssd.py``'s scan (a scalar decay a head, an update that
adds) does not, and the decay sits inside every contraction over the key
channels, which a scalar decay does not.

**The chunk form** both paths compute. Inside a chunk of ``C`` tokens with
entry state ``S_0`` and inclusive cumulative log-decays ``G_r = sum_{j<=r}
g_j`` (a channel):

    A[j, i] = beta_j sum_c k_jc k_ic exp(G_jc - G_ic)        i <  j
    P[r, j] =        sum_c q_rc k_jc exp(G_rc - G_jc)        j <= r
    U = (I + A)^{-1} (beta V - (beta K exp(G)) S_0)          the updates u_t
    O = (Q exp(G)) S_0 + P U
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

Every exponent that is formed is at most zero: the decay does not factor out
of ``A`` and ``P`` as a scalar decay does (``exp(-G)`` over a chunk overflows
float32 within a few tokens at a rate of 16), so the kernels take a chunk in
sub-blocks of :data:`SUB` rows: a sub-block's products with the columns in
front of it are referred to the sub-block's FIRST row (``exp(G_j - G_first)``
and ``exp(G_first - G_i)``, both at most one, both factors to the MXU), and
the sub-blocks on the diagonal are ``exp(G_j - G_i)`` element by element,
made and inverted SIDE BY SIDE on ``[SUB, C]`` arrays (two vector registers
at 16 x 128, where a ``[C, C]`` array in its place is sixteen of which
fourteen hold zeros): a step ``t`` of a sub-block is ONE pass — ``P``'s
column ``t`` lives on the rows at and behind ``t``, ``A``'s row ``t`` on the
rows in front, so one operand, one ``exp`` and one lane reduction give both
— and ``(I + A)^{-1}`` is the diagonal sub-blocks' inverses by substitution
on one ``[SUB, C]`` array (a row a step from the first, all sub-blocks at
once, a product and a sum over SUBLANES) put back in their places once, and
the blocks below them by the block Neumann product, which ends after
``log2(C / SUB)`` factors; nothing is left out. No ``[L, L]`` array and no
state a token exists: HBM sees q, k, v, g and beta once a pass (the
cumulative sums and the ``beta``-weighted operands are made in VMEM), o once,
and the state each chunk started from (``L / C`` of them a head, float32)
written by the differentiated forward and read by the backward.

What runs where, chosen from the platform and the shapes alone (the line
``kda: ...`` a process logs once says which, and why):

* **on a TPU, where the shapes tile** (head sizes that are whole 128-lane
  tiles, a sequence of whole chunks, no mesh that spans devices) **two Pallas
  kernels under one ``jax.custom_vjp``**: ``kda_fwd`` and ``kda_bwd`` on the
  grid ``(batch row, group of heads, chunk)``, the state (its gradient in the
  backward, which walks the chunks in reverse and makes ``A``, ``P``, the
  inverse and ``U`` again from the operands and the kept entry state) carried
  in a float32 VMEM scratch;
* **anywhere else** (the CPU, the ``test`` sizes, a ``tp`` / ``fsdp`` mesh, a
  ragged sequence) **the same chunk algebra in ``jax.numpy``**
  (:func:`_chunked`): one ``lax.scan`` over the chunks whose body is
  rematerialised, differentiated by jax, the whole lower triangle of a chunk
  element by element and the inverse a triangular solve — the reference the
  kernels are tested against (``tests/test_kda.py``, beside the recurrence
  token by token).

Precision, both paths: the cumulative log-decays, every ``exp``, the state,
``(I + A)^{-1}`` and its product with the right-hand side are float32; the
operands of the other matrix products are rounded to the inputs' dtype (bf16
on the chip) where they enter a product, with float32 accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easydl_tpu.ops import platform, remat
from easydl_tpu.ops.ssd import _NN, _NT, _TN, _dot, _free_axes, _kernel_jit
from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "kda")

#: rows of a sub-block: a bf16 sublane tile
SUB = 16
#: heads a grid cell of the kernels works through, one after another in ONE
#: basic block, so that the scheduler fills one head's latencies (the
#: substitution's steps, the small products) with another's work
CELL_HEADS = 2
_F32 = jnp.float32


def kda_flops_per_token(n_heads: int, d_k: int, d_v: int) -> float:
    """Forward FLOPs a token of the RECURRENCE as it is written (no chunk):
    three ``d_k x d_v`` products a head — ``S'^T k``, the rank-one update,
    ``S^T q``."""
    return 6.0 * d_k * d_v * n_heads


def chunk_flops_per_token(n_heads: int, d_k: int, d_v: int,
                          chunk: int) -> float:
    """Forward FLOPs a token of the CHUNK form's matrix products (what the
    kernels spend to make the result again: ``ops/remat.py``'s measure): ``A``
    and ``P`` (``2 C d_k`` each), the inverse's products (about ``2 C^2``),
    ``T R`` and ``P U`` (``2 C d_v`` each), and the four products with the
    state (``2 d_k d_v`` each)."""
    return n_heads * (4.0 * chunk * d_k + 2.0 * chunk * chunk
                      + 4.0 * chunk * d_v + 8.0 * d_k * d_v)


def gated_head_norm(y: jax.Array, z: jax.Array, weight: jax.Array,
                    eps: float) -> jax.Array:
    """``RMSNorm_head(y) * weight * sigmoid(z)``: the norm FIRST, over each
    head's channels (``y, z [..., heads, head_dim]``) with ONE gain
    ``weight [head_dim]`` shared by the heads, THEN a sigmoid gate;
    statistics in float32; returns ``y``'s dtype. (``ops/ssd.py
    gated_rmsnorm`` is the other order: a SiLU gate BEFORE a norm over a
    group's channels, with a gain a channel.)"""
    y32 = y.astype(_F32)
    normed = y32 * lax.rsqrt(jnp.mean(jnp.square(y32), -1, keepdims=True)
                             + eps)
    return (normed * weight.astype(_F32)
            * jax.nn.sigmoid(z.astype(_F32))).astype(y.dtype)


# ---------------------------------------------------------------------------
# jax.numpy: the chunk algebra, differentiated by jax
# ---------------------------------------------------------------------------

def _chunk_step(state, operands, dtype):
    """One chunk for every head: ``(S_C, O)`` from the entry state ``[B, H,
    d_k, d_v]`` float32 and the chunk's ``q, k, beta k [B, C, H, d_k]``,
    ``beta v [B, C, H, d_v]`` and cumulative log-decays ``G`` (float32)."""
    q, k, kb, vb, G = (x.astype(_F32) for x in operands)
    n = q.shape[1]
    lower = jnp.tril(jnp.ones((n, n), bool))[None, :, :, None, None]
    # exp(G_j - G_i) over the lower triangle, zero above it (the exponent is
    # masked first: above the diagonal it is positive and may overflow)
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, G[:, :, None] - G[:, None, :], 0.0)), 0.0)
    k_decayed = k[:, None, :] * decay                 # [B, j, i, H, d_k]
    A = jnp.einsum("bjhc,bjihc->bhji", kb, k_decayed)
    P = jnp.einsum("bjhc,bjihc->bhji", q, k_decayed)
    eye = jnp.eye(n, dtype=_F32)
    M = eye + A * (1.0 - eye) * jnp.tril(jnp.ones((n, n), _F32))
    e_g, last = jnp.exp(G), G[:, -1:]

    def rounded(x):
        # an operand as it enters a product: rounded to the inputs' dtype;
        # the product itself float32 (the CPU has no bf16 x bf16 -> float32
        # product of every shape, and a bf16 pair's product is exact in it)
        return x.astype(dtype).astype(_F32)

    k_bar, q_bar = rounded(kb * e_g), rounded(q * e_g)
    k_hat = rounded(k * jnp.exp(last - G))
    s = rounded(state)
    rhs = jnp.swapaxes(vb, 1, 2) - jnp.einsum("bjhc,bhcv->bhjv", k_bar, s)
    U = jax.scipy.linalg.solve_triangular(M, rhs, lower=True,
                                          unit_diagonal=True)
    u = rounded(U)
    out = jnp.einsum("bjhc,bhcv->bjhv", q_bar, s) \
        + jnp.einsum("bhji,bhiv->bjhv", rounded(P), u)
    new = state * jnp.swapaxes(jnp.exp(last), 1, 2)[:, :, 0, :, None] \
        + jnp.einsum("bihc,bhiv->bhcv", k_hat, u)
    return new, out


def _cumulative(g, chunk: int):
    """The inclusive cumulative log-decays of each chunk apart, float32."""
    b, seq = g.shape[:2]
    by_chunk = g.astype(_F32).reshape(b, seq // chunk, chunk, *g.shape[2:])
    return jnp.cumsum(by_chunk, axis=2).reshape(g.shape)


def _weighted(k, v, beta):
    """``beta k`` and ``beta v`` in the operands' dtypes."""
    beta = beta.astype(_F32)[..., None]
    return ((k.astype(_F32) * beta).astype(k.dtype),
            (v.astype(_F32) * beta).astype(v.dtype))


def _chunked(q, k, v, g, beta, *, chunk: int):
    """The chunk form in ``jax.numpy`` over whole chunks: ``(o [B, L, H,
    d_v]`` in ``v``'s dtype, the final state ``[B, H, d_k, d_v]`` float32)."""
    b, seq, heads, d_k = q.shape
    kb, vb = _weighted(k, v, beta)
    G = _cumulative(g, chunk)

    def by_chunk(x):
        return jnp.swapaxes(
            x.reshape(b, seq // chunk, chunk, *x.shape[2:]), 0, 1)

    step = jax.checkpoint(functools.partial(_chunk_step, dtype=q.dtype))
    state0 = jnp.zeros((b, heads, d_k, v.shape[-1]), _F32)
    last, out = lax.scan(step, state0, tuple(map(by_chunk, (q, k, kb, vb, G))))
    return jnp.swapaxes(out, 0, 1).reshape(v.shape).astype(v.dtype), last


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
#
# A grid cell is one chunk of CELL_HEADS heads; the kernels take q, k, v and g
# turned, ``[batch, heads, seq, size]`` — the layout the convolutions'
# kernels in front of them give, rank 4 (``lib/hlo.flash_calls`` of the
# benchmark takes a rank-3 result for a flash kernel's) — and keep the state
# TRANSPOSED, ``[d_v, d_k]``: the decay of a chunk is then a row, broadcast
# over the sublanes, and every product with the state is one of the three
# forms the MXU takes without a transpose.

def _dot32(a, b, dims):
    """A float32 product at full precision (the inverse and its use)."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _iotas(rows: int, cols: int):
    return (lax.broadcasted_iota(jnp.int32, (rows, cols), 0),
            lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _scores(q, k, kb, G, sub: int, dt):
    """A chunk's ``(A_front, A's diagonal rows, P)``: ``A``'s entries in front
    of each sub-block's own columns and ``P`` whole, ``[C, C]`` float32 each;
    and the diagonal sub-blocks of ``A`` COMPACT, side by side, as the
    substitution reads them — for every local row ``t >= 1`` one ``[SUB, C]``
    array ``W_t[j, (b, c)] = A_b[t, j]`` (``j < t``, zero below), the same
    value in all the lanes ``c`` of sub-block ``b``.

    A step ``t`` of a sub-block is ONE pass: ``P``'s column ``t`` lives on
    the rows at and behind ``t`` and ``A``'s row ``t`` on the rows in front,
    so one operand, one ``exp`` (its argument at most zero on either side)
    and one lane reduction give both."""
    n = q.shape[0]
    _, col = _iotas(sub, n)
    local = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    block = col // sub
    fronts, p_fronts = [], []
    for r0 in range(0, n, sub):
        Gr, qr, kbr = (x[r0:r0 + sub] for x in (G, q, kb))
        down = jnp.exp(Gr - G[r0:r0 + 1])
        k_back = (k * jnp.exp(jnp.minimum(G[r0:r0 + 1] - G, 0.0))).astype(dt)
        front = col < r0
        fronts.append(jnp.where(
            front, _dot((kbr * down).astype(dt), k_back, _NT), 0.0))
        p_fronts.append(jnp.where(
            front, _dot((qr * down).astype(dt), k_back, _NT), 0.0))
    # the diagonal sub-blocks' P side by side: p_diag[j, (b, t)] = P_b[j, t]
    p_diag = jnp.zeros((sub, n), _F32)
    a_rows = []
    for t in range(sub):
        behind = local >= t
        w_t = jnp.zeros((sub, n), _F32)
        for b, r0 in enumerate(range(0, n, sub)):
            Gr, qr, kr = (x[r0:r0 + sub] for x in (G, q, k))
            at = slice(r0 + t, r0 + t + 1)
            d = Gr - G[at]
            e = jnp.exp(jnp.minimum(jnp.where(behind, d, -d), 0.0))
            s = jnp.sum(jnp.where(behind, qr, kr)
                        * (jnp.where(behind, k[at], kb[at]) * e),
                        axis=1, keepdims=True)
            p_diag = jnp.where(col == r0 + t, s, p_diag)
            w_t = jnp.where(block == b, s, w_t)
        if t:
            a_rows.append(jnp.where(local < t, w_t, 0.0))
    own = local >= col % sub
    P = jnp.concatenate([
        p + jnp.where((block == b) & own, p_diag, 0.0)
        for b, p in enumerate(p_fronts)], axis=0)
    return jnp.concatenate(fronts, axis=0), a_rows, P


def _inverse(a_front, a_rows, sub: int):
    """``(I + A)^{-1} [C, C]`` float32 from :func:`_scores`' two parts of
    the strictly lower ``A``. The diagonal sub-blocks' inverses first, all at
    once and side by side, ``X_c[j, (b, c)] = X_b[j, c]`` on ONE ``[SUB, C]``
    array, a row a step from the first: ``(I + A_b) X_b = I`` gives ``X_b[t,
    :] = e_t - sum_{j < t} A_b[t, j] X_b[j, :]`` — a product and a sum over
    SUBLANES (``W_t`` is zero at the rows not yet made). Back in their places
    ``X``, then ``(D + F)^{-1} = (I + X F)^{-1} X`` with ``N = X F`` strictly
    lower by BLOCKS, so ``(I + N)^{-1} = (I - N)(I + N^2)(I + N^4) ...`` ends
    at the number of blocks."""
    n = a_front.shape[0]
    local, col = _iotas(sub, n)
    x_c = jnp.where(col % sub == local, 1.0, 0.0).astype(_F32)
    for t, w_t in enumerate(a_rows, 1):
        x_c = x_c - jnp.where(local == t, jnp.sum(
            w_t * x_c, axis=0, keepdims=True), 0.0)
    block = col // sub
    x = jnp.concatenate([jnp.where(block == b, x_c, 0.0)
                         for b in range(n // sub)], axis=0)
    if n == sub:
        return x
    step = _dot32(x, a_front, _NN)
    inv = x - _dot32(step, x, _NN)
    power, reach = step, 2
    while reach < n // sub:
        power = _dot32(power, power, _NN)
        inv = inv + _dot32(power, inv, _NN)
        reach *= 2
    return inv


def _chunk_parts(q, k, kb, vb, G, state_t, sub: int, dt):
    """What both kernels make of a chunk and its entry state ``[d_v, d_k]``:
    ``(P, T, U, the decayed operands)``."""
    a_front, a_rows, P = _scores(q, k, kb, G, sub, dt)
    T = _inverse(a_front, a_rows, sub)
    e_g, last = jnp.exp(G), G[-1:]
    tail = jnp.exp(last - G)
    k_bar, q_bar, k_hat = kb * e_g, q * e_g, k * tail
    rhs = vb - _dot(k_bar.astype(dt), state_t.astype(dt), _NT)
    U = _dot32(T, rhs, _NN)
    return P, T, U, (e_g, tail, jnp.exp(last), k_bar, q_bar, k_hat)


def _operands(refs, h: int):
    """Head ``h`` of a grid cell's blocks, float32: ``(q, k, v, beta k, beta
    v, G, beta [C, 1], the lower-triangular ones [C, C])``. The cumulative
    log-decays are made HERE, a product with the triangle of ones at full
    precision (XLA's cumulative sum over ``[L, 4096]`` float32 cost the step
    three arrays of 256 MB), and the step sizes come as a ROW a head and are
    turned by a product with the identity."""
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    q, k, v, g = (ref[h].astype(_F32) for ref in (q_ref, k_ref, v_ref, g_ref))
    n = q.shape[0]
    row, col = _iotas(n, n)
    ones = jnp.where(col <= row, 1.0, 0.0).astype(_F32)
    eye = jnp.where(col == row, 1.0, 0.0).astype(_F32)
    beta = _dot32(eye, b_ref[h:h + 1, :].astype(_F32), _NT)
    return q, k, v, k * beta, v * beta, _dot32(ones, g, _NN), beta, \
        (ones, eye)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, last_ref, *rest,
                sub: int, cell: int, keep: bool):
    state_ref = rest[-1]
    chunk = pl.program_id(2)
    dt = q_ref.dtype

    @pl.when(chunk == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    for h in range(cell):
        q, k, _, kb, vb, G, _, _ = _operands(
            (q_ref, k_ref, v_ref, g_ref, b_ref), h)
        state_t = state_ref[h]
        if keep:
            rest[0][h] = state_t
        P, _, U, (_, _, gamma, _, q_bar, k_hat) = _chunk_parts(
            q, k, kb, vb, G, state_t, sub, dt)
        u = U.astype(dt)
        out = _dot(q_bar.astype(dt), state_t.astype(dt), _NT) \
            + _dot(P.astype(dt), u, _NN)
        o_ref[h] = out.astype(o_ref.dtype)
        state_ref[h] = state_t * gamma + _dot(u, k_hat.astype(dt), _TN)

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = state_ref[...]


def _scores_bwd(q, k, kb, G, dA, dP, sub: int, dt):
    """The gradients of :func:`_scores`' ``A`` (strictly lower) and ``P``
    (lower, with its diagonal) to q, k and ``beta k``, ``[C, d_k]`` float32
    each. The gradient to ``G`` is theirs: a row's ``G`` has ``kb * dkb + q *
    dq`` of these, a column's ``- k * dk``."""
    n = q.shape[0]
    _, col = _iotas(sub, n)
    local = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    dk = jnp.zeros_like(k)
    dqs, dkbs, dk_diag = [], [], []
    for r0 in range(0, n, sub):
        Gr, qr, kbr = (x[r0:r0 + sub] for x in (G, q, kb))
        down = jnp.exp(Gr - G[r0:r0 + 1])
        back = jnp.exp(jnp.minimum(G[r0:r0 + 1] - G, 0.0))
        k_back = (k * back).astype(dt)
        dA_r, dP_r = dA[r0:r0 + sub], dP[r0:r0 + sub]
        front = col < r0
        dA_f = jnp.where(front, dA_r, 0.0).astype(dt)
        dP_f = jnp.where(front, dP_r, 0.0).astype(dt)
        dkb_r = down * _dot(dA_f, k_back, _NN)
        dq_r = down * _dot(dP_f, k_back, _NN)
        dk = dk + back * (_dot(dA_f, (kbr * down).astype(dt), _TN)
                          + _dot(dP_f, (qr * down).astype(dt), _TN))
        dk_r = jnp.zeros((sub, k.shape[1]), _F32)
        for t in range(sub):
            # column t of the diagonal sub-block: dA is zero at and above the
            # diagonal, dP above it, so the clamped exponents meet zeros there
            e = jnp.exp(jnp.minimum(Gr - G[r0 + t:r0 + t + 1], 0.0))
            w = k[r0 + t:r0 + t + 1] * e
            dA_c, dP_c = (x[:, r0 + t:r0 + t + 1] for x in (dA_r, dP_r))
            dkb_r = dkb_r + dA_c * w
            dq_r = dq_r + dP_c * w
            dk_r = dk_r + jnp.where(local == t, jnp.sum(
                (dA_c * kbr + dP_c * qr) * e, axis=0, keepdims=True), 0.0)
        dqs.append(dq_r)
        dkbs.append(dkb_r)
        dk_diag.append(dk_r)
    return (jnp.concatenate(dqs, axis=0),
            dk + jnp.concatenate(dk_diag, axis=0),
            jnp.concatenate(dkbs, axis=0))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, entry_ref, do_ref,
                dlast_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                dstate_ref, *, sub: int, cell: int):
    dt = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = dlast_ref[...]

    for h in range(cell):
        q, k, v, kb, vb, G, beta, (ones, eye) = _operands(
            (q_ref, k_ref, v_ref, g_ref, b_ref), h)
        dO = do_ref[h]
        state_t, dnew = entry_ref[h], dstate_ref[h]
        P, T, U, (e_g, tail, gamma, k_bar, q_bar, k_hat) = _chunk_parts(
            q, k, kb, vb, G, state_t, sub, dt)
        n = q.shape[0]
        row, col = _iotas(n, n)
        s, u, dnew_dt = state_t.astype(dt), U.astype(dt), dnew.astype(dt)
        dU = _dot(P.astype(dt), dO, _TN) + _dot(k_hat.astype(dt), dnew_dt, _NT)
        dk_hat = _dot(u, dnew_dt, _NN)
        dgamma = jnp.sum(state_t * dnew, axis=0, keepdims=True)
        dq_bar = _dot(dO, s, _NN)
        dP = jnp.where(col <= row, _dot(dO, u, _NT), 0.0)
        dvb = _dot32(T, dU, _TN)
        dr = dvb.astype(dt)
        dk_bar = -_dot(dr, s, _NN)
        dA = jnp.where(col < row, -_dot(dr, u, _NT), 0.0)
        dstate_ref[h] = dnew * gamma + _dot(dO, q_bar.astype(dt), _TN) \
            - _dot(dr, k_bar.astype(dt), _TN)
        dq, dk, dkb = _scores_bwd(q, k, kb, G, dA, dP, sub, dt)
        ends = jnp.sum(k_hat * dk_hat, axis=0, keepdims=True) + gamma * dgamma
        rows = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        dG = kb * dkb + q * dq - k * dk + q_bar * dq_bar + k_bar * dk_bar \
            - k_hat * dk_hat + jnp.where(rows == n - 1, ends, 0.0)
        dkb = dkb + e_g * dk_bar
        dq_ref[h] = (dq + e_g * dq_bar).astype(dq_ref.dtype)
        dk_ref[h] = (dk + tail * dk_hat + beta * dkb).astype(dk_ref.dtype)
        dv_ref[h] = (beta * dvb).astype(dv_ref.dtype)
        # a position's log-decay is in the sums of its own and every later
        # position of its chunk
        dg_ref[h] = _dot32(ones, dG, _TN)
        dbeta = jnp.sum(dkb * k, axis=1, keepdims=True) \
            + jnp.sum(dvb * v, axis=1, keepdims=True)
        db_ref[h:h + 1, :] = _dot32(dbeta, eye, _TN)


def _grid(q, chunk: int, cell: int):
    b, heads, seq, _ = q.shape
    return b, heads // cell, seq // chunk


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=48 << 20)


@_kernel_jit
def _fwd(q, k, v, g, beta, *, chunk: int, sub: int, cell: int, keep: bool,
         interpret: bool):
    """``(o [B, H, L, d_v], the final state [B, H, d_v, d_k] float32)`` and,
    with ``keep``, each chunk's entry state ``[B, H, L / C, d_v, d_k]``, from
    q, k, g ``[B, H, L, d_k]`` and v ``[B, H, L, d_v]`` — the turned layout
    the convolutions' kernels give (``ops/ssd.py``), rank 4 both ways — and
    ``beta [B, L / C, H / cell, cell, C]`` float32."""
    b, cells, chunks = grid = _grid(q, chunk, cell)
    heads, d_k, d_v = cells * cell, q.shape[-1], v.shape[-1]

    def rows(d):
        return pl.BlockSpec((None, cell, chunk, d),
                            lambda i, j, c: (i, j, c, 0))

    steps = pl.BlockSpec((None, None, None, cell, chunk),
                         lambda i, j, c: (i, c, j, 0, 0))
    state = pl.BlockSpec((None, cell, d_v, d_k), lambda i, j, c: (i, j, 0, 0))
    out_specs = [rows(d_v), state]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype),
                 jax.ShapeDtypeStruct((b, heads, d_v, d_k), _F32)]
    if keep:
        out_specs.append(pl.BlockSpec((None, cell, None, d_v, d_k),
                                      lambda i, j, c: (i, j, c, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(
            (b, heads, chunks, d_v, d_k), _F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub, cell=cell, keep=keep),
        grid=grid,
        in_specs=[rows(d_k), rows(d_k), rows(d_v), rows(d_k), steps],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((cell, d_v, d_k), _F32)],
        interpret=interpret, name="kda_fwd", compiler_params=_params(),
    )(q, k, v, g, beta)


@_kernel_jit
def _bwd(q, k, v, g, beta, entries, do, dlast, *, chunk: int, sub: int,
         cell: int, interpret: bool):
    """The gradients to q, k, v (the operands' dtypes), g and beta (float32,
    beta's in its own layout), the chunks walked from the last."""
    _, _, chunks = grid = _grid(q, chunk, cell)
    d_k, d_v = q.shape[-1], v.shape[-1]

    def rows(d):
        return pl.BlockSpec((None, cell, chunk, d),
                            lambda i, j, c: (i, j, chunks - 1 - c, 0))

    steps = pl.BlockSpec((None, None, None, cell, chunk),
                         lambda i, j, c: (i, chunks - 1 - c, j, 0, 0))
    state = pl.BlockSpec((None, cell, d_v, d_k), lambda i, j, c: (i, j, 0, 0))
    entry = pl.BlockSpec((None, cell, None, d_v, d_k),
                         lambda i, j, c: (i, j, chunks - 1 - c, 0, 0))
    operands = [rows(d_k), rows(d_k), rows(d_v), rows(d_k), steps]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub, cell=cell),
        grid=grid, in_specs=operands + [entry, rows(d_v), state],
        out_specs=operands,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM((cell, d_v, d_k), _F32)],
        interpret=interpret, name="kda_bwd", compiler_params=_params(),
    )(q, k, v, g, beta, entries, do, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernels(q, k, v, g, beta, sizes, keeps):
    return _kernels_fwd(q, k, v, g, beta, sizes, keeps, keep=False)[0]


def _kernels_fwd(q, k, v, g, beta, sizes, keeps, keep=True):
    out = _fwd(q, k, v, g, beta, keep=keep, **dict(sizes))
    if not keep:
        return (out[0], out[1]), None
    o, last, entries = out
    if keeps:
        # named HERE, so that the residuals are the named values
        # (``ops/flash_attention.py _flash_fwd`` has why)
        o = remat.name(o, remat.KDA_OUT)
        entries = remat.name(entries, remat.KDA_STATES)
    return (o, last), (q, k, v, g, beta, entries)


def _kernels_bwd(sizes, keeps, res, cotangents):
    do, dlast = cotangents
    return tuple(_bwd(*res, do.astype(res[2].dtype), dlast.astype(_F32),
                      **dict(sizes)))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def kda_kernels(q, k, v, g, beta, *, chunk: int = 128, sub: int = SUB,
                interpret: bool = False):
    """:func:`kda` by the Pallas kernels whatever the platform, on sequences
    of whole chunks. ``interpret=True`` runs them in the Pallas interpreter —
    something only a test passes, to check them against the ``jax.numpy``
    chunks and the recurrence without hardware."""
    b, seq, heads, d_k = q.shape
    d_v = v.shape[-1]
    if seq % chunk or chunk % sub:
        raise ValueError(f"the kernels take whole chunks of {chunk} in "
                         f"sub-blocks of {sub}, not a sequence of {seq}")
    cell = max(c for c in range(1, CELL_HEADS + 1) if heads % c == 0)
    sizes = (("chunk", chunk), ("sub", sub), ("cell", cell),
             ("interpret", interpret))
    # the result and the chunks' entry states: ONE candidate of a
    # rematerialised block, at what the chunk form's products cost a byte
    kept = (jax.ShapeDtypeStruct((b, heads, seq, d_v), v.dtype),
            jax.ShapeDtypeStruct((b, heads, seq // chunk, d_v, d_k), _F32))
    keeps = remat.kernel_keeps(
        "kda", kept, (remat.KDA_OUT, remat.KDA_STATES),
        b * seq * chunk_flops_per_token(heads, d_k, d_v, chunk))

    def turned(x):  # [B, L, H, d] <-> [B, H, L, d]
        return jnp.swapaxes(x, 1, 2)

    # the step sizes a ROW a head and chunk: [B, L / C, H / cell, cell, C]
    steps = jnp.transpose(beta.astype(_F32).reshape(
        b, seq // chunk, chunk, heads // cell, cell), (0, 1, 3, 4, 2))
    o, last_t = _kernels(turned(q), turned(k), turned(v),
                         turned(g.astype(_F32)), steps, sizes, keeps)
    return turned(o), jnp.swapaxes(last_t, -1, -2)


def untiled(seq: int, d_k: int, d_v: int, chunk: int) -> Optional[str]:
    """Why the kernels cannot take these shapes, or None."""
    if d_k % 128 or d_v % 128:
        return f"head sizes {d_k} / {d_v} are no whole 128-lane tiles"
    if seq % chunk:
        return f"a sequence of {seq} is no whole number of chunks of {chunk}"
    if chunk % SUB:
        return f"a chunk of {chunk} is no whole number of sub-blocks of {SUB}"
    if _free_axes()[1]:
        return "a mesh that spans devices (the kernels are not per shard yet)"
    return None


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, *, chunk: int = 128,
        impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """The recurrence above over whole sequences from a zero state: by the
    Pallas kernels on a TPU where the shapes tile, else by the ``jax.numpy``
    chunks (the module's docstring has both and the precision they share).
    Logs once which path a shape took.

    Args:
      q, k: ``[batch, seq, heads, d_k]``, as the recurrence takes them (the
        caller has normed and scaled them).
      v: ``[batch, seq, heads, d_v]``.
      g: ``[batch, seq, heads, d_k]`` log-decays, at most zero.
      beta: ``[batch, seq, heads]`` step sizes.
      chunk: tokens a chunk, on either path; a sequence that is no multiple
        of it is padded at its end with ``g = 0`` and ``beta = 0`` (no decay,
        no update: the padded positions change no state) and the result cut
        back, by the ``jax.numpy`` chunks.
      impl: ``"auto"`` | ``"xla"`` (the ``jax.numpy`` chunks whatever the
        platform).

    Returns ``(o`` of ``v``'s shape and dtype, the final state ``[batch,
    heads, d_k, d_v]`` float32).
    """
    if impl not in ("auto", "xla"):
        raise ValueError(f"unknown kda impl {impl!r}")
    batch, seq, heads, d_k = q.shape
    d_v = v.shape[-1]
    n = min(chunk, seq)
    pad = -seq % n
    said = (f"{(seq + pad) // n} chunks of {n} a sequence, {heads} heads of "
            f"{d_k} / {d_v}, a decay a channel, matmul operands "
            f"{q.dtype.name}, decays, state and inverse float32")
    why = ("impl='xla'" if impl == "xla" else "no tpu"
           if not platform.on_tpu() else untiled(seq, d_k, d_v, n))
    if why is None:
        log_once(log, f"kda: Pallas kernels kda_fwd / kda_bwd, {said}; a grid "
                      f"cell is one chunk of {CELL_HEADS} heads in sub-blocks "
                      f"of {SUB}, the diagonal ones made and inverted side by "
                      f"side on [{SUB}, {n}] arrays, the state carried in "
                      f"VMEM, every chunk's entry state kept for the backward")
        return kda_kernels(q, k, v, g, beta, chunk=n)
    log_once(log, f"kda: chunks in jax.numpy, not the kernels ({why}), "
                  f"{said}, differentiated by jax (body rematerialised)")
    if pad:
        q, k, v, g, beta = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (
            x.ndim - 2)) for x in (q, k, v, g, beta))
    o, last = _chunked(q, k, v, g, beta, chunk=n)
    return (o[:, :seq] if pad else o), last

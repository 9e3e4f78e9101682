"""Mamba-2's selective state-space scan in its chunked (state-space dual)
form, the causal depthwise convolution in front of it and the gated RMSNorm
behind it (Dao and Gu 2024, "Transformers are SSMs"; the layer as the HF
``GraniteMoeHybrid`` / ``Mamba2`` modelling files write it).

The recurrence, per head with state ``h`` of ``[head_dim, d_state]``:

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T        y_t = h_t C_t + D * x_t

is linear in ``h``, so a chunk of ``Q`` positions can be done with matrix
multiplications: inside the chunk ``Y = (L o C B^T) (dt * X)`` with the decay
matrix ``L[i, j] = exp(sum_{j<k<=i} dt_k A)`` for ``i >= j``; what the chunk
leaves behind is ``B^T (decay_to_end o dt * X)``; what it inherits is
``C h_in`` decayed from the chunk's start. :func:`ssd_scan` walks the chunks
carrying the state, so only one chunk's decay matrices exist at a time, and
keeps for the backward pass the operands and the state each chunk started
from (4 MB a chunk of a ``[2, ...]`` microbatch at 64 heads of 64 and a state
of 128: 67 MB a 4k sequence in chunks of 256, 268 MB an 8k one in chunks of
128) and nothing else: the rest is made again.

What runs where, chosen from the platform and the shapes alone (the line
``ssd: ...`` a process logs once says which, and why):

* **on a TPU, where the shapes tile** (:func:`untiled`: a group's heads in
  cells of eight that fill whole 128-lane tiles, state and chunk multiples of
  128, a sequence of whole chunks — 64 heads of 64 at a state of 128 in
  chunks of 128 or 256, both benchmark cells') **two Pallas kernels under one
  ``jax.custom_vjp``**: ``ssd_fwd`` and ``ssd_bwd``, on the grid ``(batch
  row, chunk, cell of heads)``. A chunk's decay matrices, masked scores and
  the carried state (its gradient in the backward, which walks the chunks in
  reverse and makes the decay matrices and scores again from the operands)
  stay in VMEM; ``C B^T`` is made once a group; HBM sees x, B, C, dt and the
  log-decay sums read once, y written once, and each chunk's entry state
  written by the forward and read by the backward. Under a mesh whose batch
  or ``tp`` axes span devices the call is per shard (``jax.shard_map``:
  GSPMD cannot partition a Mosaic kernel), heads over ``tp``;
* **anywhere else** (the CPU, the ``test`` presets' 16-wide shapes, a ragged
  sequence) **the same algorithm in ``jax.numpy``**: one ``lax.scan`` over
  the chunks whose body is rematerialised, differentiated by jax — the
  reference the kernels are tested against (``tests/test_ssd_kernels.py``).

Precision, the contract of both paths: the decay exponents, their cumulative
sums, the decay matrix and the carried state are float32 whatever the inputs
are; the operands of the four matrix multiplications (``dt * X``, the masked
decayed scores, the inputs decayed to the chunk's end, the state in front of
``C h``) are rounded to the inputs' dtype (bf16 on the chip) where they enter
a product, with float32 accumulation.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from easydl_tpu.core.mesh_shapes import BATCH_AXES
from easydl_tpu.ops import platform
from easydl_tpu.ops.attention import HEAD_AXIS
from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "ssd")


def ssd_flops_per_token(n_heads: int, head_dim: int, d_state: int,
                        n_groups: int, chunk: int) -> float:
    """Forward FLOPs of :func:`ssd_scan` a token: per chunk of ``Q`` the
    ``C B^T`` scores (``2 Q^2 N`` a GROUP: the heads of a group share them,
    so eight groups pay eight times one group's and 64 heads still pay them
    eight times, not 64), the intra-chunk product (``2 Q^2 P`` a head), the
    state the chunk leaves and the part it inherits (``2 Q P N`` a head
    each); the causal half of the two ``Q^2`` products is counted in full,
    as the attention convention has it."""
    return (2.0 * chunk * d_state * n_groups
            + 2.0 * chunk * head_dim * n_heads
            + 4.0 * head_dim * d_state * n_heads)


def causal_conv1d(x: jax.Array, weight: jax.Array, bias=None) -> jax.Array:
    """Depthwise causal convolution over the sequence: ``y[t] = sum_k
    weight[k] * x[t - (K - 1) + k] (+ bias)``, positions before the start
    read as zero. ``x`` is ``[batch, seq, *channels]``, ``weight``
    ``[K, *channels]`` (tap ``K - 1`` multiplies the current position, the
    layout of a torch ``Conv1d`` weight read along its last axis). Written
    as ``K`` shifted multiply-adds, which XLA fuses into one pass; a width
    of 4 gives a convolution nothing to win."""
    taps, seq = weight.shape[0], x.shape[1]
    pad = [(0, 0), (taps - 1, 0)] + [(0, 0)] * (x.ndim - 2)
    padded = jnp.pad(x, pad)
    w = weight.astype(x.dtype)
    y = sum(lax.slice_in_dim(padded, k, k + seq, axis=1) * w[k]
            for k in range(taps))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return y


def gated_rmsnorm(y: jax.Array, z: jax.Array, weight: jax.Array,
                  eps: float, groups: int = 1) -> jax.Array:
    """``RMSNorm(y * silu(z)) * weight``, the gate applied BEFORE the norm,
    statistics in float32; returns ``y``'s dtype. ``weight``'s axes are the
    mixer's inner channels (``[heads, head_dim]``); the mean square is taken
    over all of them (``groups`` 1: GraniteMoeHybrid's gated norm) or, the
    first of them split into ``groups``, over each group's channels apart
    (Mamba-2's and NemotronH's ``group_size = inner / n_groups``: 512 of
    4,096 at eight groups)."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    lead = g.ndim - weight.ndim
    if groups == 1:
        var = jnp.mean(jnp.square(g), axis=tuple(range(lead, g.ndim)),
                       keepdims=True)
        normed = g * lax.rsqrt(var + eps)
    else:
        if weight.shape[0] % groups:
            raise ValueError(f"{weight.shape[0]} heads do not divide into "
                             f"{groups} norm groups")
        by_group = g.reshape(g.shape[:lead] + (groups, -1))
        var = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
        normed = (by_group * lax.rsqrt(var + eps)).reshape(g.shape)
    out = normed * weight.astype(jnp.float32)
    return out.astype(y.dtype)


def _prepared(x, dt, B, C, chunk: int):
    """The sizes both paths share, and the operands as chunks want them:
    ``(q, n_chunks, pad, operands padded to whole chunks)``. A sequence that
    is no multiple of the chunk is padded at its end with ``dt = 0`` (no
    decay, no input: the padded positions change no state)."""
    seq = x.shape[1]
    q = min(chunk, seq)
    n_chunks = -(-seq // q)
    pad = n_chunks * q - seq

    def whole(a):
        if not pad:
            return a
        return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))

    return q, n_chunks, pad, tuple(whole(a) for a in (x, dt, B, C))


def _scan_reference(x, dt, A, B, C, D, *, q: int, n_chunks: int):
    """The chunked scan in ``jax.numpy``: one ``lax.scan`` over the chunks
    that carries the state, its body rematerialised, differentiated by jax.
    Operands hold whole chunks."""
    batch, _, heads, head_dim = x.shape
    groups, d_state = B.shape[2], B.shape[3]
    rep = heads // groups
    cd = x.dtype

    def chunks(a):  # [batch, seq, ...] -> [n_chunks, batch, q, ...]
        a = a.reshape((batch, n_chunks, q) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    A = A.astype(jnp.float32)
    D = D.astype(jnp.float32)
    causal = jnp.tril(jnp.ones((q, q), jnp.bool_))

    def body(state, inputs):
        xc, dtc, Bc, Cc = inputs
        dtc = dtc.astype(jnp.float32)
        # log-decay up to and including each position, [batch, q, heads]
        cs = jnp.cumsum(dtc * A, axis=1)
        x_dt = (xc.astype(jnp.float32) * dtc[..., None]).astype(cd)
        # inside the chunk: (L o C B^T) (dt X). The exponent is masked
        # BEFORE the exponential: above the diagonal it is positive and may
        # overflow, and inf * 0 is not 0.
        scores = jnp.einsum("bign,bjgn->bgij", Cc, Bc,
                            preferred_element_type=jnp.float32)
        cs_h = jnp.moveaxis(cs, 1, 2)  # [batch, heads, q]
        expo = cs_h[..., :, None] - cs_h[..., None, :]
        decay = jnp.exp(jnp.where(causal, expo, -jnp.inf))
        mixed = (jnp.repeat(scores, rep, axis=1) * decay).astype(cd)
        y = jnp.einsum("bhij,bjhp->bihp", mixed, x_dt,
                       preferred_element_type=jnp.float32)
        # what the chunk inherits: C h_in, decayed from the chunk's start
        carried = jnp.einsum(
            "bign,bgrpn->bigrp", Cc,
            state.astype(cd).reshape(batch, groups, rep, head_dim, d_state),
            preferred_element_type=jnp.float32,
        ).reshape(batch, q, heads, head_dim)
        y = y + carried * jnp.exp(cs)[..., None]
        y = y + xc.astype(jnp.float32) * D[:, None]
        # what the chunk leaves: its inputs decayed to the chunk's end
        to_end = jnp.exp(cs[:, -1:, :] - cs)  # [batch, q, heads]
        x_end = (xc.astype(jnp.float32) * (dtc * to_end)[..., None]).astype(cd)
        left = jnp.einsum(
            "bjgrp,bjgn->bgrpn",
            x_end.reshape(batch, q, groups, rep, head_dim), Bc,
            preferred_element_type=jnp.float32,
        ).reshape(batch, heads, head_dim, d_state)
        state = state * jnp.exp(cs[:, -1, :])[:, :, None, None] + left
        return state, y.astype(cd)

    state0 = jnp.zeros((batch, heads, head_dim, d_state), jnp.float32)
    _, y = lax.scan(jax.checkpoint(body), state0,
                    (chunks(x), chunks(dt), chunks(B), chunks(C)))
    return jnp.moveaxis(y, 0, 1).reshape(batch, n_chunks * q, heads, head_dim)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

#: the most heads a grid cell takes: each is a stretch of straight-line code
_CELL_HEADS = 8

#: what the compiler allows a kernel of VMEM where the call names no limit;
#: the calls ask for what their blocks hold and this much for a head's
#: ``[Q, Q]`` tiles in flight
_DEFAULT_VMEM = 16 << 20

_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _cell_heads(rep: int) -> int:
    """Heads a grid cell: the most of ONE group's ``rep`` heads that divide
    them, up to ``_CELL_HEADS``."""
    return max(c for c in range(1, min(rep, _CELL_HEADS) + 1) if rep % c == 0)


def untiled(heads: int, head_dim: int, groups: int, d_state: int, q: int,
            pad: int) -> Optional[str]:
    """Why the kernels cannot take these shapes on the chip, or None where
    they can: a cell's heads are whole sublanes of the per-head rows, a
    head's channels whole packed sublanes of its ``[P, Q]`` tile, the state
    and the chunk lane tiles."""
    cell = _cell_heads(heads // groups)
    if pad:
        return f"the sequence is no multiple of the chunk ({q})"
    if cell % 8 or head_dim % 16:
        return (f"{cell} heads of {head_dim} a cell ({heads} heads in "
                f"{groups} groups) are no whole tiles")
    if d_state % 128 or q % 128:
        return f"state {d_state} or chunk {q} is no multiple of 128 lanes"
    return None


def _head_rows(cell: int, d: int):
    """The sublane slice of each head of a cell's ``[cell · P, ...]``
    array."""
    return [slice(h * d, (h + 1) * d) for h in range(cell)]


def _stacked(parts):
    """A cell's heads one under the other, one operand of a product."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _chunk_terms(dt_ref, cs_ref):
    """What a chunk's kernels need of its per-head rows ``[cell, Q]``
    (float32): ``dt`` and the log-decay sums, the decay from each position
    to the chunk's end and the growth ``exp(cs)`` from its start as rows;
    the sums turned once into columns ``[Q, cell]`` (a decay matrix takes
    both); the whole chunk's log-decay as a ``[cell, 1]`` column."""
    cs = cs_ref[...]
    end = cs[:, -1:]
    return dict(dt=dt_ref[...], cs=cs, cs_cols=cs.T, end=end,
                to_end=jnp.exp(end - cs), grown=jnp.exp(cs))


def _decay_t(terms, h: int, causal_t):
    """Head ``h``'s decay matrix turned, ``[j, i] -> exp(cs_i - cs_j)`` for
    ``i >= j`` and zero else — masked BEFORE the exponential: there the
    exponent is positive and may overflow, and inf * 0 is not 0."""
    expo = terms["cs"][h:h + 1, :] - terms["cs_cols"][:, h:h + 1]
    return jnp.exp(jnp.where(causal_t, expo, -jnp.inf))


def _causal_t(q: int):
    return (lax.broadcasted_iota(jnp.int32, (q, q), 1)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 0))


def _decayed(state, end, rows):
    """``state [cell · P, N]`` with each head's part decayed through the
    whole chunk, ``end [cell, 1]`` its log. The sums are spread over the
    lanes BEFORE the exponential and a head's row over its sublanes after
    it: Mosaic broadcasts along one of the two at a time, and folds two
    broadcasts with nothing between them into one it refuses."""
    whole = jnp.exp(jnp.broadcast_to(end, (end.shape[0], state.shape[1])))
    return _stacked([state[r] * whole[h:h + 1, :]
                     for h, r in enumerate(rows)])


# Both kernels hold a chunk TURNED, and take and give it so: a head's
# channels ``P`` along the sublanes, the chunk's positions along the lanes —
# ``x``, ``y`` and their gradients are ``[batch, heads, P, seq]`` in HBM.
# That is the order XLA itself keeps these arrays in around the scan
# (``[batch, seq, 64, 64]`` as ``{1,3,2,0}``: a head of 64 would fill half a
# lane tile; read in both cells' compiled steps, parent and change), so the
# transposes in front of the call and behind it are views, where rows of
# ``heads · P`` lanes cost a copy each way and a turn on the XLU in the
# kernel (the first tree of PR 43: 21 ms of a 431 ms Nemotron step in copies
# read under ``conv1d`` and ``gated_norm``). Turned, a head is ``x_ref[h]``,
# its per-position numbers (dt, the decays) are rows that broadcast over
# sublanes for nothing, sums over a head's channels are elementwise across
# vregs and leave rows, and the products are plain: ``Y^T = (dt X)^T M^T``,
# ``(C h)^T = h C^T``, ``left = (decay dt X)^T B`` with the state as ``[P,
# N]`` a head. Held the other way every per-position number is a ``[Q, 1]``
# column to spread over the lanes, on the XLU, 64 vregs a head. The one
# column left is ``cs_j`` in the decay matrix. The score tile is turned too:
# ``S^T = B C^T``. B and C are ``[batch, groups, seq, N]``, XLA's order too.


def _fwd_kernel(skip_ref, x_ref, b_ref, c_ref, dt_ref, cs_ref, y_ref, *rest,
                per_group: int):
    """One chunk of one cell's heads. ``rest``: the chunk's entry state as a
    result, where the backward will want it; then the scratch."""
    # skip_ref: [1, H] float32 in SMEM; x_ref, y_ref: [cell, P, Q]; b_ref,
    # c_ref: [Q, N] of the cell's group; dt_ref, cs_ref: [cell, Q] float32;
    # entry_ref: [cell · P, N] float32. Scratch: state_ref [cells, cell · P,
    # N] float32, the carried state; scores_ref [Q, Q] float32, B C^T.
    *entry_ref, state_ref, scores_ref = rest
    ci = pl.program_id(2)
    cell, head_dim, q = x_ref.shape
    cd = x_ref.dtype
    rows = _head_rows(cell, head_dim)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[ci] = jnp.zeros(state_ref.shape[1:], jnp.float32)

    @pl.when(ci % per_group == 0)
    def _():  # once a group: its heads share the scores
        scores_ref[...] = _dot(b_ref[...], c_ref[...], _NT)

    state = state_ref[ci]
    if entry_ref:
        entry_ref[0][...] = state
    t = _chunk_terms(dt_ref, cs_ref)
    causal_t = _causal_t(q)
    scores_t = scores_ref[...]
    # what the chunk inherits, before its decay from the chunk's start
    carried_t = _dot(state.astype(cd), c_ref[...], _NT)
    x_ends = []
    for h, r in enumerate(rows):
        row = slice(h, h + 1)
        x = x_ref[h].astype(jnp.float32)
        x_dt = (x * t["dt"][row]).astype(cd)
        mixed_t = (scores_t * _decay_t(t, h, causal_t)).astype(cd)
        y = _dot(x_dt, mixed_t, _NN)
        y = y + carried_t[r] * t["grown"][row]
        y_ref[h] = (y + x * skip_ref[0, ci * cell + h]).astype(cd)
        x_ends.append((x * (t["dt"][row] * t["to_end"][row])).astype(cd))
    left = _dot(_stacked(x_ends), b_ref[...], _NN)
    state_ref[ci] = _decayed(state, t["end"], rows) + left


def _bwd_kernel(skip_ref, x_ref, b_ref, c_ref, dt_ref, cs_ref, dy_ref,
                entry_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref,
                dskip_ref, dstate_ref, scores_ref, dscores_ref, db_sum_ref,
                dc_sum_ref, *, per_group: int):
    """One chunk of one cell's heads, the chunks in reverse: the decay
    matrices and scores made again from the operands, the gradient of the
    carried state in VMEM."""
    # operands as the forward's; dy_ref: [cell, P, Q]; entry_ref: [cell · P,
    # N] float32, the state the chunk started from. Results: dx_ref as x;
    # db_ref, dc_ref [Q, N], written at a group's last cell; [cell, Q]
    # float32 rows: ddt_ref (what reaches dt other than through the decay
    # sums), dcs_ref, dskip_ref (the skip weight's gradient by position).
    # Scratch: dstate_ref [cells, cell · P, N], the gradient by the state the
    # chunk leaves; scores_ref, dscores_ref [Q, Q] and db_sum_ref, dc_sum_ref
    # [Q, N], a group's float32 sums.
    ci = pl.program_id(2)
    cell, head_dim, q = x_ref.shape
    cd = x_ref.dtype
    rows = _head_rows(cell, head_dim)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate_ref[ci] = jnp.zeros(dstate_ref.shape[1:], jnp.float32)

    @pl.when(ci % per_group == 0)
    def _():
        scores_ref[...] = _dot(b_ref[...], c_ref[...], _NT)
        dscores_ref[...] = jnp.zeros_like(dscores_ref)
        db_sum_ref[...] = jnp.zeros_like(db_sum_ref)
        dc_sum_ref[...] = jnp.zeros_like(dc_sum_ref)

    state, dstate = entry_ref[...], dstate_ref[ci]
    state_cd, dstate_cd = state.astype(cd), dstate.astype(cd)
    t = _chunk_terms(dt_ref, cs_ref)
    causal_t = _causal_t(q)
    last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    scores_t = scores_ref[...]
    carried_t = _dot(state_cd, c_ref[...], _NT)
    dx_end_t = _dot(dstate_cd, b_ref[...], _NT)
    dscores_t = jnp.zeros((q, q), jnp.float32)
    ddts, dcss, dskips, dgrown, x_ends = [], [], [], [], []
    for h, r in enumerate(rows):
        row = slice(h, h + 1)
        dt, to_end, grown = (t[k][row] for k in ("dt", "to_end", "grown"))
        into_end = dt * to_end
        x, dy_cd, dxe = x_ref[h].astype(jnp.float32), dy_ref[h], dx_end_t[r]
        dy = dy_cd.astype(jnp.float32)
        x_dt = (x * dt).astype(cd)
        decay_t = _decay_t(t, h, causal_t)
        mixed_t = (scores_t * decay_t).astype(cd)
        dx_dt = _dot(dy_cd, mixed_t, _NT)
        dscores_t = dscores_t + _dot(x_dt, dy_cd, _TN) * decay_t
        # what reaches cs_i - cs_j is d mixed o mixed, [j, i]: its sum over j
        # is dy_i . (M x_dt)_i, the chunk's own part of y made again, and
        # over i (x_dt)_j . (M^T dy)_j, which dx_dt holds — both rows, and
        # both of the ROUNDED operands, so that what one adds at i the other
        # takes at j to the last bit of the products
        inside = _dot(x_dt, mixed_t, _NN) + carried_t[r] * grown
        by_x = jnp.sum(dx_dt * x, axis=0, keepdims=True)
        back = jnp.sum(dx_dt * x_dt.astype(jnp.float32), axis=0,
                       keepdims=True)
        by_x_end = jnp.sum(dxe * x, axis=0, keepdims=True)
        by_end = by_x_end * into_end
        # the chunk's last position takes what reaches exp(cs_end): every
        # position's decay to the end and the entry state's through the chunk
        at_end = (jnp.sum(by_end, axis=1, keepdims=True)
                  + jnp.exp(t["end"][row])
                  * jnp.sum(dstate[r] * state[r], keepdims=True))
        dcss.append(jnp.sum(dy * inside, axis=0, keepdims=True)
                    - back - by_end + jnp.where(last, at_end, 0.0))
        ddts.append(by_x + by_x_end * to_end)
        dx_ref[h] = (dx_dt * dt + dxe * into_end
                     + dy * skip_ref[0, ci * cell + h]).astype(cd)
        dskips.append(jnp.sum(dy * x, axis=0, keepdims=True))
        dgrown.append((dy * grown).astype(cd))
        x_ends.append((x * into_end).astype(cd))
    ddt_ref[...] = _stacked(ddts)
    dskip_ref[...] = _stacked(dskips)
    dcs_ref[...] = _stacked(dcss)
    dcarried_t, x_end_t = _stacked(dgrown), _stacked(x_ends)
    dstate_ref[ci] = (_decayed(dstate, t["end"], rows)
                      + _dot(dcarried_t, c_ref[...], _NN))
    dc_sum_ref[...] += _dot(dcarried_t, state_cd, _TN)
    db_sum_ref[...] += _dot(x_end_t, dstate_cd, _TN)
    dscores_ref[...] += dscores_t

    @pl.when(ci % per_group == per_group - 1)
    def _():
        ds_t = dscores_ref[...].astype(cd)
        db_ref[...] = (db_sum_ref[...] + _dot(ds_t, c_ref[...], _NN)).astype(cd)
        dc_ref[...] = (dc_sum_ref[...] + _dot(ds_t, b_ref[...], _TN)).astype(cd)


def _specs(n_chunks: int, q: int, head_dim: int, d_state: int, cell: int,
           per_group: int, *, reverse: bool):
    """The block specs of the grid ``(batch row, chunk, cell)``, the chunks
    walked backwards where ``reverse``: ``(the skip weights', x's, B's, the
    per-head rows', the state's)``."""
    def at(c):
        return n_chunks - 1 - c if reverse else c

    return (
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((None, cell, head_dim, q),
                     lambda b, c, i: (b, i, 0, at(c))),
        pl.BlockSpec((None, None, q, d_state),
                     lambda b, c, i: (b, i // per_group, at(c), 0)),
        pl.BlockSpec((None, cell, q), lambda b, c, i: (b, i, at(c))),
        pl.BlockSpec((None, None, cell * head_dim, d_state),
                     lambda b, c, i: (b, at(c), i, 0)),
    )


def _params(held: int):
    """The chunk and cell axes carry the state and a group's sums: both
    sequential."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=held + _DEFAULT_VMEM)


def _sizes(x, B, q: int):
    """``(chunks a sequence, heads a cell, cells a group)`` of the kernels'
    views ``x [batch, H, P, seq]`` and ``B [batch, G, seq, N]``."""
    cell = _cell_heads(x.shape[1] // B.shape[1])
    return x.shape[3] // q, cell, x.shape[1] // B.shape[1] // cell


def _fwd(skip, x, B, C, dt_rows, cs_rows, *, q: int, keep: bool,
         interpret: bool):
    """``y``, and where ``keep`` every chunk's entry state beside it."""
    # skip: [1, H] float32; x: [batch, H, P, seq]; B, C: [batch, G, seq, N];
    # dt_rows, cs_rows: [batch, H, seq] float32
    batch, heads, head_dim, _ = x.shape
    d_state = B.shape[3]
    n_chunks, cell, per_group = _sizes(x, B, q)
    lanes = cell * head_dim
    scalars, mine, group, rows, entry = _specs(
        n_chunks, q, head_dim, d_state, cell, per_group, reverse=False)
    out_specs, out_shape = [mine], [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if keep:
        out_specs.append(entry)
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, n_chunks, heads * head_dim, d_state), jnp.float32))
    # x and y, B and C, the rows and the entry state by block, two buffers
    # each; the carried state and the scores once
    held = (2 * (2 * q * (lanes + d_state) * x.dtype.itemsize
                 + 2 * cell * q * 4 + d_state * lanes * 4)
            + d_state * heads * head_dim * 4 + q * q * 4)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per_group=per_group),
        grid=(batch, n_chunks, heads // cell),
        in_specs=[scalars, mine, group, group, rows, rows],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads // cell, lanes, d_state),
                                   jnp.float32),
                        pltpu.VMEM((q, q), jnp.float32)],
        interpret=interpret, name="ssd_fwd", compiler_params=_params(held),
    )(skip, x, B, C, dt_rows, cs_rows)


def _bwd(skip, x, B, C, dt_rows, cs_rows, dy, entries, *, q: int,
         interpret: bool):
    """``dx, dB, dC`` as the operands and three ``[batch, H, seq]`` float32
    rows: ``ddt`` (but for what reaches ``dt`` through the decay sums),
    ``dcs`` and the skip weight's gradient by position."""
    batch, heads, head_dim, _ = x.shape
    d_state = B.shape[3]
    n_chunks, cell, per_group = _sizes(x, B, q)
    lanes = cell * head_dim
    scalars, mine, group, rows, entry = _specs(
        n_chunks, q, head_dim, d_state, cell, per_group, reverse=True)
    f32 = jnp.float32
    by_head = jax.ShapeDtypeStruct(dt_rows.shape, f32)
    # x, dy and dx, B, C, dB and dC, the rows in and out and the entry state
    # by block, two buffers each; the state's gradient, a group's four sums
    held = (2 * ((3 * q * lanes + 4 * q * d_state) * x.dtype.itemsize
                 + 5 * cell * q * 4 + d_state * lanes * 4)
            + d_state * heads * head_dim * 4 + 2 * q * (q + d_state) * 4)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, per_group=per_group),
        grid=(batch, n_chunks, heads // cell),
        in_specs=[scalars, mine, group, group, rows, rows, mine, entry],
        out_specs=[mine, group, group, rows, rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(B.shape, B.dtype),
            jax.ShapeDtypeStruct(C.shape, C.dtype),
            by_head, by_head, by_head,
        ],
        scratch_shapes=[
            pltpu.VMEM((heads // cell, lanes, d_state), f32),
            pltpu.VMEM((q, q), f32), pltpu.VMEM((q, q), f32),
            pltpu.VMEM((q, d_state), f32), pltpu.VMEM((q, d_state), f32),
        ],
        interpret=interpret, name="ssd_bwd", compiler_params=_params(held),
    )(skip, x, B, C, dt_rows, cs_rows, dy, entries)


def _chunk_sums(a, q: int, *, reverse: bool = False):
    """Running sums of ``a [..., seq]`` (float32) inside each chunk of ``q``
    along the last axis, from the chunk's start up to and including each
    position, or where ``reverse`` from each position to the chunk's end: a
    product with a triangle of ones at the HIGHEST precision — float32
    arithmetic (a float32 is three bfloat16 pieces exactly, a one times a
    piece is exact, the sums are float32) — where XLA's ``cumsum`` over the
    minor dimension is a ``reduce-window`` that costs ``q`` adds an element
    (22 ms of the hybrid's step at chunks of 256: PERF.md section 6)."""
    ones = jnp.ones((q, q), jnp.float32)
    into = jnp.tril(ones) if reverse else jnp.triu(ones)  # [from k, into i]
    sums = jnp.einsum("...k,ki->...i", a.reshape(a.shape[:-1] + (-1, q)),
                      into, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    return sums.reshape(a.shape)


def _rows(dt, A, q: int):
    """``dt`` and the log-decay sums up to and including each position of
    its chunk as ``[batch, heads, seq]`` float32 rows: 256 bytes a token at
    64 heads, made by XLA in front of the kernels (which keeps ``dt`` in
    this order already: the swap is a view)."""
    dt = jnp.swapaxes(dt.astype(jnp.float32), 1, 2)
    return dt, _chunk_sums(dt * A[:, None], q)


def _turned(x):
    """``[batch, seq, heads, P] -> [batch, heads, P, seq]``, and back."""
    return jnp.transpose(x, (0, 2, 3, 1))


def _unturned(x_t):
    return jnp.transpose(x_t, (0, 3, 1, 2))


def _made(*arrays):
    """``arrays`` as their producers leave them, before the kernels' views
    of them: without the barrier XLA folds a view (a bitcast in its own
    order) into the fusion that makes the array and names the fusion after
    it, so the convolutions' and the gated norm's passes read as ``ssd``'s
    in a trace. The arrays are kernel operands: they are written either
    way."""
    return lax.optimization_barrier(arrays)


def _views(x, B, C, D):
    """The kernels' views of the operands: the skip weights as a row of
    scalars, x turned, B and C ``[batch, groups, seq, N]``."""
    x, B, C = _made(x, B, C)
    return (D.astype(jnp.float32)[None], _turned(x),
            jnp.swapaxes(B, 1, 2), jnp.swapaxes(C, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_kernels(x, dt, A, B, C, D, q, interpret):
    return _scan_kernels_fwd(x, dt, A, B, C, D, q, interpret, keep=False)[0]


def _scan_kernels_fwd(x, dt, A, B, C, D, q, interpret, keep=True):
    out = _fwd(*_views(x, B, C, D), *_rows(dt, A, q), q=q, keep=keep,
               interpret=interpret)
    # what the backward takes: the operands and every chunk's entry state —
    # what the jax.numpy scan's backward is handed too
    return _unturned(out[0]), (x, dt, A, B, C, D, *out[1:])


def _scan_kernels_bwd(q, interpret, res, dy):
    x, dt, A, B, C, D, entries = res
    dt_rows, cs_rows = _rows(dt, A, q)
    dx, dB, dC, ddt, dcs, dskip = _bwd(
        *_views(x, B, C, D), dt_rows, cs_rows, _turned(*_made(dy)), entries,
        q=q, interpret=interpret)
    # a position's log-decay is in the sums of its own and every later
    # position of its chunk
    da = _chunk_sums(dcs, q, reverse=True)
    ddt = jnp.swapaxes(ddt + da * A[:, None], 1, 2)
    return (_unturned(dx), ddt.astype(dt.dtype),
            jnp.sum(da * dt_rows, axis=(0, 2)), jnp.swapaxes(dB, 1, 2),
            jnp.swapaxes(dC, 1, 2),
            jnp.sum(dskip, axis=(0, 2)).astype(D.dtype))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _free_axes():
    """``(context mesh, its axes that span devices and are not manual)``."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh, [a for a in mesh.axis_names
                  if a not in mesh.manual_axes and mesh.shape[a] > 1]


def _head_split(heads: int, groups: int):
    """How a call under the context mesh splits its heads: ``(ways, the
    axis the heads go over or None, the axis B's and C's groups go over or
    None)``. Heads go over ``tp`` with their groups where both divide, or
    with ONE group whole on every shard; else every shard computes all
    heads."""
    mesh, free = _free_axes()
    if HEAD_AXIS not in free:
        return 1, None, None
    n = mesh.shape[HEAD_AXIS]
    if heads % n == 0 and groups % n == 0:
        return n, HEAD_AXIS, HEAD_AXIS
    if heads % n == 0 and groups == 1:
        return n, HEAD_AXIS, None
    log_once(log, f"ssd: {heads} heads in {groups} groups do not divide over "
                  f"{HEAD_AXIS}={n}; every shard computes all heads")
    return 1, None, None


def _per_shard(fn, x, B):
    """Wrap ``fn(x, dt, A, B, C, D)`` in ``jax.shard_map`` over the context
    mesh where that mesh spans more than one device (GSPMD cannot partition
    a Mosaic kernel), as ``ops/attention.py _per_shard`` wraps the flash
    kernels: batch over the mesh's batch axes, heads as :func:`_head_split`
    says. An axis that does not divide is left out of the specs: those
    devices compute the whole of it. Axes that are manual already are per
    shard already."""
    mesh, free = _free_axes()
    if not free:
        return fn
    batch = tuple(a for a in BATCH_AXES if a in free)
    if batch and x.shape[0] % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    _, over, by_group = _head_split(x.shape[2], B.shape[2])
    rows, head = P(batch or None, None, over), P(over)
    group = P(batch or None, None, by_group)
    return jax.shard_map(fn, in_specs=(rows, rows, head, group, group, head),
                         out_specs=rows, check_vma=False)


def ssd_scan_kernels(x, dt, A, B, C, D, *, chunk: int = 256,
                     interpret: bool = False) -> jax.Array:
    """:func:`ssd_scan` by the Pallas kernels whatever the platform, on
    sequences that are whole chunks. ``interpret=True`` runs them in the
    Pallas interpreter — something only a test passes, to check them
    against the ``jax.numpy`` scan without hardware."""
    q = min(chunk, x.shape[1])
    if x.shape[1] % q:
        raise ValueError(f"the kernels take whole chunks of {q}, not a "
                         f"sequence of {x.shape[1]}")

    def kernels(x, dt, A, B, C, D):
        return _scan_kernels(x, dt, A.astype(jnp.float32), B, C, D, q,
                             interpret)

    return _per_shard(kernels, x, B)(x, dt, A, B, C, D)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, *, chunk: int = 256) -> jax.Array:
    """The scan above over whole sequences from a zero state: by the Pallas
    kernels on a TPU where the shapes tile, else by the ``jax.numpy`` scan
    (the module's docstring has both, what each keeps for the backward, and
    the precision they share). Logs once which path a shape took.

    Args:
      x: ``[batch, seq, heads, head_dim]``, the compute dtype.
      dt: ``[batch, seq, heads]`` step sizes, already positive (softplus).
      A: ``[heads]``, negative.
      B, C: ``[batch, seq, groups, d_state]``; head ``h`` reads group
        ``h // (heads // groups)``.
      D: ``[heads]`` skip weight.
      chunk: positions a chunk, on either path; a sequence that is no
        multiple of it is padded at its end with ``dt = 0`` (no decay, no
        input: the padded positions change no state) and the result cut
        back, by the ``jax.numpy`` scan.

    Returns ``y`` of ``x``'s shape and dtype.
    """
    batch, seq, heads, head_dim = x.shape
    groups, d_state = B.shape[2], B.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups")
    q, n_chunks, pad, (xp, dtp, Bp, Cp) = _prepared(x, dt, B, C, chunk)
    said = (f"{n_chunks} chunks of {q} a sequence, {heads} heads of "
            f"{head_dim} in {groups} B/C groups of state {d_state}, matmul "
            f"operands {x.dtype.name}, decay and state float32")
    # what a shard of the call holds decides, where a mesh splits the heads
    ways, _, by_group = _head_split(heads, groups)
    mine = (heads // ways, head_dim, groups // ways if by_group else groups)
    why = ("no tpu" if not platform.on_tpu()
           else untiled(*mine, d_state, q, pad))
    if why is None:
        cell = _cell_heads(mine[0] // mine[2])
        log_once(log, f"ssd: Pallas kernels ssd_fwd / ssd_bwd, {said}; a "
                      f"grid cell is one chunk of {cell} heads, the state "
                      f"carried in VMEM, every chunk's entry state kept for "
                      f"the backward")
        return ssd_scan_kernels(x, dt, A, B, C, D, chunk=q)
    log_once(log, f"ssd: chunked scan in jax.numpy, not the kernels ({why}), "
                  f"{said}, differentiated by jax (body rematerialised)")
    y = _scan_reference(xp, dtp, A, Bp, Cp, D, q=q, n_chunks=n_chunks)
    return y[:, :seq] if pad else y

"""Mamba-2's selective state-space scan in its chunked (state-space dual)
form, the causal depthwise convolutions in front of it and the gated RMSNorm
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

The three convolutions in front of the scan (over x, B and C, each with its
bias and SiLU) go the same two ways, :func:`causal_conv1d_silu` choosing from
the platform and the shapes alone (the line ``conv1d: ...`` a process logs
once a shape says which, and why):

* **on a TPU, where the shapes tile** (:func:`conv_untiled`: taps that reach
  back no further than one tile, channels that are whole sublane tiles, a
  sequence of whole tiles — both cells') **two Pallas kernels under one
  ``jax.custom_vjp``**: ``conv1d_fwd`` reads the array once and writes
  ``silu(sum_k w[k] x[p - (K - 1) + k] + b)`` once; ``conv1d_bwd`` reads x
  and dy once, makes the pre-activation again, writes dx once and sums the
  taps' and the bias's gradients in a float32 VMEM scratch across the
  sequence, one tile of sums a batch row. The rule keeps x, the taps and the
  bias and nothing else — what remat ``full`` makes again anyway. Under a
  mesh per shard, x's channels over ``tp`` with the heads, B and C whole;
* **anywhere else** ``silu(causal_conv1d(x, w, b))``, differentiated by jax:
  the reference the kernels are tested against
  (``tests/test_conv_kernels.py``).

Their precision: taps and bias are rounded to the input's dtype on both
paths; the kernels make products, sums and the SiLU in float32 and round ONCE
to the input's dtype (jax.numpy rounds every operation), and their taps' and
bias's sums are float32 over the whole sequence.
"""

from __future__ import annotations

import functools
import inspect
import math
from typing import NamedTuple, Optional

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


# The kernels' calls are ``jax.jit``s of their own inside the step: a kernel's
# body is hundreds to thousands of operations written out head by head or
# tile by tile, and a step traces a call once a USE (the pass, the one remat
# makes again, each run of layers, each of the benchmark's programs) —
# jitted, once a shape: the convolutions' bodies cost a Nemotron run's set-up
# 34 s before, the scan's nine traces a step program 1.5 s (PERF.md section 6,
# PR 44).
def _kernel_jit(call):
    """``call`` jitted, its keyword-only arguments the static ones."""
    return jax.jit(call, static_argnames=[
        name for name, p in inspect.signature(call).parameters.items()
        if p.kind is p.KEYWORD_ONLY])


@_kernel_jit
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


@_kernel_jit
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


def _batch_split(rows: int):
    """``(context mesh, its axes that span devices, those of them a batch
    of rows goes over)``: the mesh's batch axes, or none of them where they
    do not divide the rows (those devices then compute all of them)."""
    mesh, free = _free_axes()
    batch = tuple(a for a in BATCH_AXES if a in free)
    if batch and rows % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    return mesh, free, batch


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
    _, free, batch = _batch_split(x.shape[0])
    if not free:
        return fn
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


# ---------------------------------------------------------------------------
# the convolutions' kernels
# ---------------------------------------------------------------------------
#
# ``silu(causal_conv1d(x, w, b))`` as one pass over the array forward and one
# backward. Like the scan's kernels they take an array in the order XLA keeps
# it in: x — ``[batch, seq, 64, 64]``, kept with the SEQUENCE along the lanes
# — turned, ``[batch, 1, heads · P, seq]``, the taps' shift a rotation along
# the lanes; B and C — a state of 128 fills a lane tile — as ``[batch,
# groups, seq, N]``, the shift along the sublanes (rank 4 both ways: the
# benchmark's ``lib/hlo.flash_calls`` takes a rank-3 result for a flash
# kernel's). One body serves both, the sequence's axis of a block a static
# argument. A grid cell takes a block and works through it in STRIPS, a strip
# tile by tile along the sequence: a tile shifted by ``s`` positions is its
# own rotation below position ``s`` patched with the rotation of the tile in
# front of it, so a rotation serves two tiles and what lives is a tile's. In
# front of a strip's first tile stands the block's own tile, or the last tile
# of the block before it — the same array handed a second time under a
# clipped block index, as the band kernels hand their neighbour
# (``ops/flash_attention.py _band_neighbours``) — or zeros in front of the
# sequence. Taps and bias travel HALVED (:func:`_half_pre_activation`) as one
# float32 tile a stretch of channels, numbers ``0 .. K`` along the sequence's
# axis, and the backward's sums come back in the same tile.

#: the most a strip holds ``(tiles of channels, positions of the sequence)``:
#: sublane tiles of the dtype by lanes where the sequence lies along the
#: lanes, lane tiles by rows where it lies along the sublanes. A strip is one
#: pass of a loop the compiler does not overlap with the next, so it pays
#: each pass's latencies once a strip: on the chip a strip of 128 float32
#: vregs an array beat one of 32 by a quarter, for all its register spills
#: (PERF.md section 6, PR 44)
_STRIP = {1: (2, 4096), 0: (1, 256)}
#: the most strips a block holds ``(across the channels, along the
#: sequence)``: a MiB at bfloat16, a DMA that hides a grid step's fixed cost
_BLOCK_STRIPS = {1: (2, 2), 0: (1, 16)}


class _ConvTiles(NamedTuple):
    """How the convolution's kernels cut a view ``[batch, groups, channels,
    seq]`` (``axis`` 1: the sequence along the lanes) or ``[batch, groups,
    seq, channels]`` (``axis`` 0). ``strip`` and ``block`` are ``(channels,
    seq)``."""
    axis: int
    edge: int    # the sequence's extent of the tile in front of a strip
    tile: int    # and of the tile the taps and the sums travel in
    strip: tuple
    block: tuple

    def order(self, channels, seq):
        """``(channels, seq)`` in the view's own order."""
        return (channels, seq) if self.axis else (seq, channels)


def _fit(n: int, unit: int, most: int) -> int:
    """The largest of ``unit, 2 unit, .. most unit`` that divides ``n``."""
    return max(unit * m for m in range(1, most + 1) if n % (unit * m) == 0)


def conv_untiled(x, taps: int):
    """``(why the kernels cannot take x [batch, seq, *channels] on the chip
    or None, how they cut it)``. The sequence lies along the sublanes where
    the last channel axis fills whole lane tiles, else along the lanes —
    XLA's own choice for both; the taps reach back no further than one tile,
    the channels are whole sublane tiles where the sequence has the lanes,
    the sequence whole tiles of its own axis."""
    axis, sub = int(x.shape[-1] % 128 != 0), 32 // x.dtype.itemsize
    seq = x.shape[1]
    channels = math.prod(x.shape[2:]) if axis else x.shape[-1]
    edge, tile, width = (128, 128, sub) if axis else (sub, 8, 128)
    if taps - 1 > edge or taps + 1 > tile:
        return f"{taps} taps reach past a tile of {min(edge, tile)}", None
    if channels % width:
        return f"{channels} channels are no whole tiles of {sub} sublanes", None
    if seq % edge:
        return (f"a sequence of {seq} is no whole tiles of {edge} "
                f"{'lanes' if axis else 'sublanes'}"), None
    tiles, positions = _STRIP[axis]
    strip = (_fit(channels, width, tiles), _fit(seq, edge, positions // edge))
    across, along = _BLOCK_STRIPS[axis]
    block = (_fit(channels, strip[0], across), _fit(seq, strip[1], along))
    return None, _ConvTiles(axis, edge, tile, strip, block)


def _at(t: _ConvTiles, channels, seq, *lead):
    """A block's index of ``channels`` and ``seq``, each ``(start, size)``,
    in the view's own order behind ``lead``."""
    return (*lead, *t.order(pl.ds(*channels), pl.ds(*seq)))


def _strips(t: _ConvTiles, body, *, reverse: bool = False):
    """``body(a strip's channels (start, size), its sequence's (start, size),
    whether a strip of the block lies in front of it)`` for every strip of a
    block, a stretch of channels' strips in the sequence's order, or against
    it where ``reverse``."""
    (width, length), n = t.strip, t.block[1] // t.strip[1]

    def one(i, carry):
        j = n - 1 - i % n if reverse else i % n
        body((pl.multiple_of(i // n * width, width), width),
             (pl.multiple_of(j * length, length), length), j > 0)
        return carry

    lax.fori_loop(0, t.block[0] // width * n, one, None)


def _x_tiles(x_ref, before_ref, t, channels, seq, inside, first):
    """A strip of x as float32 tiles along the sequence, the tile in front of
    it first: the block's own where the strip lies ``inside`` the block, else
    the neighbour's, or zeros at the sequence's ``first`` block."""
    start = pl.multiple_of(jnp.maximum(seq[0] - t.edge, 0), t.edge)
    mine = x_ref[_at(t, channels, (start, t.edge))]
    theirs = before_ref[_at(t, channels, (0, t.edge))]
    before = jnp.where(inside, mine,
                       jnp.where(first, jnp.zeros_like(theirs), theirs))
    return [before.astype(jnp.float32)] + _tiles(
        x_ref[_at(t, channels, seq)].astype(jnp.float32), t)


def _tiles(a, t: _ConvTiles):
    """A strip cut into tiles along the sequence."""
    return [lax.slice_in_dim(a, i, i + t.edge, axis=t.axis)
            for i in range(0, a.shape[t.axis], t.edge)]


def _turns(tile, n_taps: int, t: _ConvTiles, *, ahead: bool = False):
    """``tile`` rotated along the sequence by ``s = 0 .. K - 1`` positions
    towards its end, or towards its start where ``ahead``."""
    return [tile] + [pltpu.roll(tile, t.edge - s if ahead else s, t.axis)
                     for s in range(1, n_taps)]


def _from_other(t: _ConvTiles, width: int, n_taps: int, *,
                ahead: bool = False):
    """Where a tile shifted by ``s = 1 .. K - 1`` positions reads the tile
    in front of it (behind it where ``ahead``): its first (last) ``s``
    positions."""
    position = lax.broadcasted_iota(jnp.int32, t.order(width, t.edge), t.axis)
    return [None] + [position >= t.edge - s if ahead else position < s
                     for s in range(1, n_taps)]


def _shifted(own, other, from_other):
    """A tile shifted along the sequence, ``[p] -> tile[p - s]`` for ``s = 0
    .. K - 1`` (or ``tile[p + s]``), from its own turns and those of the
    tile in front of it (behind it): a vreg's rotation serves both
    neighbours, and nothing lives longer than a tile."""
    return [own[0]] + [jnp.where(mask, theirs, mine) for mask, theirs, mine
                       in zip(from_other[1:], other[1:], own[1:])]


def _taps(taps_ref, t: _ConvTiles, channels, n_taps: int):
    """A stretch of channels' ``K + 1`` numbers: columns or rows that spread
    over the sequence."""
    tile = taps_ref[_at(t, channels, (0, t.tile))]
    return [lax.slice_in_dim(tile, k, k + 1, axis=t.axis)
            for k in range(n_taps + 1)]


def _half_pre_activation(taps, back):
    """``h = (sum_k w[k] x[p - (K - 1) + k] + b) / 2`` with ``back[s] = x[p -
    s]``, the taps' tile holding HALF the taps and bias (a float's half is
    exact, so ``2 h`` is the pre-activation to the bit). Both kernels want
    ``h``: ``sigmoid(pre) = (1 + tanh(h)) / 2`` costs the vector unit two
    operations where ``1 / (1 + exp(-pre))`` costs it a division (ten, with
    its special cases), and never overflows."""
    half = taps[-1]
    for tap, x in zip(taps[-2::-1], back):
        half = half + tap * x
    return half


def _conv_fwd_kernel(x_ref, before_ref, taps_ref, y_ref, *, t: _ConvTiles,
                     n_taps: int):
    # x_ref, y_ref: a block; before_ref: the tile in front of it (the block's
    # own first tile at the sequence's start, where it is not read);
    # taps_ref: the block's channels' tile of taps and bias, float32
    first = pl.program_id(3) == 0
    from_before = _from_other(t, t.strip[0], n_taps)

    def strip(channels, seq, inside):
        tiles = _x_tiles(x_ref, before_ref, t, channels, seq, inside, first)
        taps = _taps(taps_ref, t, channels, n_taps)
        before, ys = _turns(tiles[0], n_taps, t), []
        for x in tiles[1:]:  # a tile at a time: what lives is a tile's
            own = _turns(x, n_taps, t)
            half = _half_pre_activation(
                taps, _shifted(own, before, from_before))
            # y = pre sigmoid(pre) = h (1 + tanh(h))
            ys.append((half + half * jnp.tanh(half)).astype(y_ref.dtype))
            before = own
        y_ref[_at(t, channels, seq)] = jnp.concatenate(ys, axis=t.axis)

    _strips(t, strip)


def _conv_bwd_kernel(x_ref, before_ref, dy_ref, taps_ref, dx_ref, sums_ref,
                     after_ref, acc_ref, *, t: _ConvTiles, n_taps: int):
    # The grid walks a sequence's blocks from the last to the first, a block
    # its strips and a strip its tiles. Results: dx_ref, a block; sums_ref, a
    # batch row's sums over the sequence of 2 dpre x[p - (K - 1) + k]
    # (numbers 0 .. K - 1) and of 2 dpre (number K) in the taps' tile, written
    # with the sequence's first block. Scratch, float32: after_ref [block's
    # channels, edge], 2 dpre in the tile behind the strip at hand; acc_ref
    # [K + 1, block's channels, edge], the sums by position in a tile.
    step = pl.program_id(3)
    from_before = _from_other(t, t.strip[0], n_taps)
    from_after = _from_other(t, t.strip[0], n_taps, ahead=True)

    @pl.when(step == 0)
    def _():  # nothing lies behind a sequence's end
        after_ref[...] = jnp.zeros_like(after_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def strip(channels, seq, inside):
        tiles = _x_tiles(x_ref, before_ref, t, channels, seq, inside,
                         step == pl.num_programs(3) - 1)
        dys = _tiles(dy_ref[_at(t, channels, seq)], t)
        taps = _taps(taps_ref, t, channels, n_taps)
        behind = _at(t, channels, (0, t.edge))
        before, dpres = _turns(tiles[0], n_taps, t), []
        for x, dy in zip(tiles[1:], dys):
            own = _turns(x, n_taps, t)
            half = _half_pre_activation(
                taps, _shifted(own, before, from_before))
            # silu'(pre) = sigmoid (1 + pre (1 - sigmoid))
            #            = (1 + tanh(h) + h (1 - tanh(h)^2)) / 2
            # TWICE dpre from here on: the halved taps make dx of it as it
            # is, and the sums are halved once, outside
            th = jnp.tanh(half)
            dpres.append(dy.astype(jnp.float32) * (
                1.0 + th + half * (1.0 - th * th)))
            before = own
        after = _turns(after_ref[behind], n_taps, t, ahead=True)
        sums, dxs = [0.0] * (n_taps + 1), []
        for x, dpre in zip(tiles[:0:-1], dpres[::-1]):
            turned = _turns(dpre, n_taps, t, ahead=True)
            # dx[p] = sum_s w[K - 1 - s] dpre[p + s], and the tap's own sum
            # over p of dpre[p] x[p - s] is that of dpre[p + s] x[p] (past
            # the sequence's end dpre is zero, in front of its start x)
            ahead = _shifted(turned, after, from_after)
            dx = sum(tap * a for tap, a in zip(taps[-2::-1], ahead))
            sums = [total + a * x for total, a in zip(
                sums, ahead[::-1])] + [sums[n_taps] + dpre]
            dxs.append(dx.astype(dx_ref.dtype))
            after = turned
        after_ref[behind] = after[0]
        dx_ref[_at(t, channels, seq)] = jnp.concatenate(dxs[::-1],
                                                        axis=t.axis)
        for k, partial in enumerate(sums):
            acc_ref[_at(t, channels, (0, t.edge), k)] += partial

    _strips(t, strip, reverse=True)

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        number = lax.broadcasted_iota(jnp.int32, sums_ref.shape, t.axis)
        sums = jnp.zeros(sums_ref.shape, jnp.float32)
        for k in range(n_taps + 1):
            sums = jnp.where(number == k, jnp.sum(
                acc_ref[k], axis=t.axis, keepdims=True), sums)
        sums_ref[...] = sums


def _conv_call(kernel, name: str, x, t: _ConvTiles, *, reverse: bool,
               blocks: int, interpret: bool):
    """``(the pallas_call of kernel on the grid (batch row, group, block of
    channels, block of the sequence) — the sequence walked backwards, one
    block after the other, where reverse —, the specs of: a block, the tile
    in front of it, a batch row's tile of sums, the taps' tile)``. ``blocks``:
    how many blocks the call holds at a time."""
    channels, seq = t.order(*x.shape[2:])
    n, per = seq // t.block[1], t.block[1] // t.edge

    def at(i):
        return n - 1 - i if reverse else i

    def spec(extent, where):
        return pl.BlockSpec(
            (None, None, *t.order(t.block[0], extent)),
            lambda b, g, c, i: (b, g, *t.order(c, where(i))))

    specs = (spec(t.block[1], at),
             spec(t.edge, lambda i: jnp.maximum(at(i) * per - 1, 0)),
             spec(t.tile, lambda i: 0),
             pl.BlockSpec((None, *t.order(t.block[0], t.tile)),
                          lambda b, g, c, i: (g, *t.order(c, 0))))
    held = 2 * blocks * math.prod(t.block) * x.dtype.itemsize
    return functools.partial(
        pl.pallas_call, kernel, name=name, interpret=interpret,
        grid=(*x.shape[:2], channels // t.block[0], n),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3
            + ("arbitrary" if reverse else "parallel",),
            vmem_limit_bytes=held + _DEFAULT_VMEM)), specs


@_kernel_jit
def _conv_fwd(x, taps, *, t: _ConvTiles, n_taps: int, interpret: bool):
    """``y`` as ``x``, a view ``[batch, groups, *t.order(channels, seq)]``;
    ``taps``: ``[groups, *t.order(channels, tile)]`` float32."""
    call, (own, before, _, tile) = _conv_call(
        functools.partial(_conv_fwd_kernel, t=t, n_taps=n_taps),
        "conv1d_fwd", x, t, reverse=False, blocks=2, interpret=interpret)
    return call(in_specs=[own, before, tile], out_specs=own,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x, x, taps)


@_kernel_jit
def _conv_bwd(x, dy, taps, *, t: _ConvTiles, n_taps: int, interpret: bool):
    """``dx`` as ``x`` and every batch row's sums ``[batch, groups,
    *t.order(channels, tile)]`` float32."""
    channels, _ = t.order(*x.shape[2:])
    call, (own, before, sums, tile) = _conv_call(
        functools.partial(_conv_bwd_kernel, t=t, n_taps=n_taps),
        "conv1d_bwd", x, t, reverse=True, blocks=3, interpret=interpret)
    f32 = jnp.float32
    return call(
        in_specs=[own, before, own, tile], out_specs=[own, sums],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (*x.shape[:2], *t.order(channels, t.tile)), f32)],
        scratch_shapes=[pltpu.VMEM(t.order(t.block[0], t.edge), f32),
                        pltpu.VMEM((n_taps + 1, *t.order(t.block[0], t.edge)),
                                   f32)],
    )(x, x, dy, taps)


def _conv_view(a, t: _ConvTiles):
    """The kernels' view of ``a [batch, seq, *channels]``: ``[batch, 1,
    channels, seq]``, turned, or ``[batch, groups, seq, N]``."""
    batch, seq = a.shape[:2]
    if t.axis:
        return jnp.moveaxis(a, 1, -1).reshape(batch, 1, -1, seq)
    return jnp.swapaxes(a.reshape(batch, seq, -1, a.shape[-1]), 1, 2)


def _conv_unview(v, shape, t: _ConvTiles):
    """``[batch, seq, *channels]`` (``shape``) of a view."""
    if t.axis:
        return jnp.moveaxis(v.reshape(shape[:1] + shape[2:] + shape[1:2]),
                            -1, 1)
    return jnp.swapaxes(v, 1, 2).reshape(shape)


def _tap_tiles(x, weight, bias, t: _ConvTiles):
    """HALF the taps and bias (:func:`_half_pre_activation`), rounded to
    ``x``'s dtype first as ``causal_conv1d`` rounds them, as the kernels'
    float32 tiles: ``[1, channels, tile]`` or ``[groups, tile, N]``, numbers
    ``0 .. K`` first along the tile."""
    numbers = 0.5 * jnp.concatenate(
        [weight.astype(x.dtype), bias.astype(x.dtype)[None]]
    ).astype(jnp.float32)
    k1 = numbers.shape[0]
    if t.axis:  # [1, channels, K + 1]
        tiles = numbers.reshape(k1, -1).T[None]
    else:  # [groups, K + 1, N]
        tiles = jnp.swapaxes(numbers.reshape(k1, -1, numbers.shape[-1]), 0, 1)
    pad = [(0, 0)] * 3
    pad[1 + t.axis] = (0, t.tile - k1)
    return jnp.pad(tiles, pad)


def _tap_unview(tiles, shape, t: _ConvTiles):
    """``[K + 1, *channels]`` (``shape``) of tiles as :func:`_tap_tiles`
    lays them out."""
    numbers = lax.slice_in_dim(tiles, 0, shape[0], axis=1 + t.axis)
    return jnp.moveaxis(numbers, 1 + t.axis, 0).reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_kernels(x, weight, bias, interpret):
    return _conv_kernels_fwd(x, weight, bias, interpret)[0]


def _conv_kernels_fwd(x, weight, bias, interpret):
    n_taps = weight.shape[0]
    why, t = conv_untiled(x, n_taps)
    if why:
        raise ValueError(f"the convolution's kernels cannot take "
                         f"{x.shape}: {why}")
    y = _conv_fwd(_conv_view(*_made(x), t), _tap_tiles(x, weight, bias, t),
                  t=t, n_taps=n_taps, interpret=interpret)
    # what the backward takes: x, the taps and the bias, and makes the
    # pre-activation again from them — what remat `full` makes again anyway
    return _conv_unview(y, x.shape, t), (x, weight, bias)


def _conv_kernels_bwd(interpret, res, dy):
    x, weight, bias = res
    n_taps = weight.shape[0]
    _, t = conv_untiled(x, n_taps)
    x_made, dy = _made(x, dy)
    dx, sums = _conv_bwd(
        _conv_view(x_made, t), _conv_view(dy, t),
        _tap_tiles(x, weight, bias, t), t=t, n_taps=n_taps,
        interpret=interpret)
    sums = 0.5 * _tap_unview(jnp.sum(sums, axis=0),
                             (n_taps + 1,) + x.shape[2:], t)
    return (_conv_unview(dx, x.shape, t), sums[:n_taps].astype(weight.dtype),
            sums[n_taps].astype(bias.dtype))


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


def _conv_split(x):
    """``(the mesh axis x's first channel axis goes over under the context
    mesh or None, how many ways)``: ``tp`` where ``x`` is of the turned kind —
    the heads' — and divides; B and C (``ssm_group`` is whole on every
    device: ``core/sharding.py``) stay whole."""
    mesh, free = _free_axes()
    if (x.shape[-1] % 128 and HEAD_AXIS in free
            and x.shape[2] % mesh.shape[HEAD_AXIS] == 0):
        return HEAD_AXIS, mesh.shape[HEAD_AXIS]
    return None, 1


def _conv_per_shard(fn, x):
    """Wrap ``fn(x, weight, bias)`` in ``jax.shard_map`` where the context
    mesh spans devices, as :func:`_per_shard` wraps the scan: batch over the
    mesh's batch axes, the channels as :func:`_conv_split` says."""
    _, free, batch = _batch_split(x.shape[0])
    if not free:
        return fn
    over, _ = _conv_split(x)
    rows = P(batch or None, None, over)
    return jax.shard_map(fn, in_specs=(rows, P(None, over), P(over)),
                         out_specs=rows, check_vma=False)


def causal_conv1d_silu_kernels(x, weight, bias=None, *,
                               interpret: bool = False) -> jax.Array:
    """:func:`causal_conv1d_silu` by the Pallas kernels whatever the
    platform, on shapes that tile (:func:`conv_untiled`). ``interpret=True``
    runs them in the Pallas interpreter — something only a test passes."""
    if bias is None:
        bias = jnp.zeros(weight.shape[1:], weight.dtype)

    def kernels(x, weight, bias):
        return _conv_kernels(x, weight, bias, interpret)

    return _conv_per_shard(kernels, x)(x, weight, bias)


def causal_conv1d_silu(x: jax.Array, weight: jax.Array,
                       bias=None) -> jax.Array:
    """``silu(causal_conv1d(x, weight, bias))``, the Mamba-2 mixer's
    convolution: by the Pallas kernels on a TPU where the shapes tile, one
    pass over the array forward and one backward, else as written here,
    differentiated by jax (the module's docstring has both). Logs once
    which path a shape took."""
    taps = weight.shape[0]
    _, ways = _conv_split(x)
    mine = jax.ShapeDtypeStruct(
        x.shape[:2] + (x.shape[2] // ways,) + x.shape[3:], x.dtype)
    why, t = ("no tpu", None) if not platform.on_tpu() \
        else conv_untiled(mine, taps)
    said = (f"{taps} taps, bias and SiLU over {list(mine.shape)} "
            f"{x.dtype.name}, products and sums float32")
    if why is None:
        log_once(log, f"conv1d: Pallas kernels conv1d_fwd / conv1d_bwd, "
                      f"{said}; the sequence along the "
                      f"{'lanes' if t.axis else 'sublanes'}, blocks of "
                      f"{t.block[0]} channels by {t.block[1]} positions in "
                      f"strips of {t.strip[0]} by {t.strip[1]}; the backward "
                      f"keeps x and makes the pre-activation again")
        return causal_conv1d_silu_kernels(x, weight, bias)
    log_once(log, f"conv1d: jax.numpy, not the kernels ({why}), {said}, "
                  f"differentiated by jax")
    return jax.nn.silu(causal_conv1d(x, weight, bias))

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
with one ``lax.scan`` that carries the state, so only one chunk's
``[batch, heads, Q, Q]`` decay matrix exists at a time (537 MB in float32
for all 16 chunks of a ``[2, 4096]`` microbatch at 64 heads, 34 MB for one),
and the scan's body is rematerialised: the backward pass keeps the carried
state of each chunk (4 MB) and computes the rest again. JAX differentiates it.

Precision: the decay exponents, their cumulative sums, the decay matrix and
the carried state are float32 whatever the inputs are; the operands of the
four matrix multiplications are in the inputs' dtype (bf16 on the chip) with
float32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, *, chunk: int = 256) -> jax.Array:
    """The scan above over whole sequences from a zero state.

    Args:
      x: ``[batch, seq, heads, head_dim]``, the compute dtype.
      dt: ``[batch, seq, heads]`` step sizes, already positive (softplus).
      A: ``[heads]``, negative.
      B, C: ``[batch, seq, groups, d_state]``; head ``h`` reads group
        ``h // (heads // groups)``.
      D: ``[heads]`` skip weight.
      chunk: positions a chunk; a sequence that is no multiple of it is
        padded at its end with ``dt = 0`` (no decay, no input: the padded
        positions change no state) and the result cut back.

    Returns ``y`` of ``x``'s shape and dtype.
    """
    batch, seq, heads, head_dim = x.shape
    groups, d_state = B.shape[2], B.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups")
    rep = heads // groups
    q = min(chunk, seq)
    n_chunks = -(-seq // q)
    pad = n_chunks * q - seq
    cd = x.dtype
    log_once(log, f"ssd: chunked scan in jax.numpy, {n_chunks} chunks of {q} "
                  f"a sequence, {heads} heads in {groups} B/C groups, matmul "
                  f"operands {cd.name}, decay and state float32, "
                  f"differentiated by jax (body rematerialised)")

    def chunks(a):  # [batch, seq, ...] -> [n_chunks, batch, q, ...]
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
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
    y = jnp.moveaxis(y, 0, 1).reshape(batch, n_chunks * q, heads, head_dim)
    return y[:, :seq] if pad else y

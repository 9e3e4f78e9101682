"""Chunked, fused softmax cross-entropy for large-vocab LM heads.

The naive LM loss materializes the full logits tensor ``[B, S, V]`` in f32
(the Granite hybrid at microbatch 2, seq 4096, vocab 100352: 3.1 GiB beside
the model's state on a 16 GB chip). This op never builds it: the head
matmul, log-sum-exp and target-pick run chunk-by-chunk over the sequence
inside a ``lax.scan``, and a chunk's logits live for one scan step only:

- evaluation (no gradient asked, under ``jit``) is one product a chunk,
  nothing kept;
- under differentiation (a ``jax.custom_vjp``) the forward rule forms the
  loss AND both gradients in the one pass that holds a chunk's logits:
  ``dlogits = (softmax - onehot) * mask * logit_scale / count`` goes
  straight into ``d_hidden = dlogits x head`` and ``d_head += dlogits^T x
  hidden`` — three products a chunk, no logits recomputed, none stashed.
  The residuals are the two gradients (``[B, S, D]`` and ``[V, D]``, in the
  inputs' dtypes); the backward rule scales them by the incoming cotangent;
- every product takes its operands in the inputs' dtype (bf16 on TPU) and
  accumulates in f32 (``preferred_element_type``); ``d_head`` rides the
  scan in the head's dtype, as jax's transpose of the scan carried it: an
  f32 carry measured 9% slower on a v5e (its traffic doubles) for a sum of
  8 chunks that is rounded to the head's dtype in the end anyway.

A chunk is sized in ROWS (``B x positions``), because rows are what the head
gradient's product contracts over: each chunk reads and writes the whole
``[V, D]`` carry, so few rows a chunk make the backward a memory pass.

Numerics are those of ``optax.softmax_cross_entropy_with_integer_labels``
(loss = lse(logits) − logits[target], f32 accumulation throughout); the op
is differentiable w.r.t. both ``hidden`` and ``head``, in REVERSE MODE ONLY
(``jax.jvp``, ``jacfwd`` and a Hessian through it raise TypeError, as through
any ``custom_vjp``). The forward rule forms both gradients whether or not
they are asked for; what nobody reads (a frozen head's, both in evaluation)
is dropped by XLA inside one jitted program, product and carry, and by
nothing outside one. A ``jax.checkpoint`` around the loss re-runs the pass.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from easydl_tpu.core.mesh_shapes import BATCH_AXES
from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "fused_xent")

#: Rows (sequences on one device x positions) of a chunk when the caller
#: names no ``chunk_size``: the ``[V, D]`` carry of the head's gradient is
#: read and written once a chunk, and at a thousand rows that traffic sits
#: under the time of the product that fills it (v5e, bf16: 2 V D bytes each
#: way at 819 GB/s against 2 rows V D FLOPs at 197 TFLOP/s). Measured there
#: at [2, 4096, 2048] x [100352, 2048], bf16: 512 / 1,024 / 2,048 rows take
#: 74 / 68 / 76 ms for the loss and both gradients (PERF.md, PR 26).
CHUNK_ROWS = 1024
#: ... and never more f32 logits a chunk than this (1,024 rows of a 100k
#: vocabulary are 0.38 GiB; a 256k vocabulary gets 512 rows).
CHUNK_LOGITS_BYTES = 512 * 1024 ** 2


def local_batch(batch: int) -> int:
    """One device's share of ``batch`` sequences under the context mesh (the
    one ``Trainer`` enters): split over the mesh's batch axes where it
    divides, whole where it does not."""
    mesh = jax.sharding.get_abstract_mesh()
    shards = math.prod(mesh.shape[a] for a in BATCH_AXES
                       if a in mesh.axis_names)
    return batch if batch % shards else batch // shards


def chunk_positions(batch: int, seq: int, vocab: int) -> int:
    """Positions a chunk for ``[batch, seq, vocab]`` logits: the sequence in
    the fewest equal chunks of at most :data:`CHUNK_ROWS` rows and
    :data:`CHUNK_LOGITS_BYTES` of f32 logits on one device (one position at
    the least)."""
    rows = local_batch(batch)
    most = max(1, min(CHUNK_ROWS // rows,
                      CHUNK_LOGITS_BYTES // (4 * rows * vocab)))
    return math.ceil(seq / math.ceil(seq / most))


def _logits(h, head, logit_scale):
    # [B, C, V] — f32 accumulation on the MXU, inputs stay bf16
    logits = lax.dot_general(
        h, head, dimension_numbers=(((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits * logit_scale if logit_scale != 1.0 else logits


def _nll(logits, t):
    """Per-position ``lse``, negative log-likelihood and the target's
    one-hot (a compare against an iota: nothing gathered or scattered)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = lax.broadcasted_iota(jnp.int32, logits.shape, 2) == t[..., None]
    return lse, lse - jnp.where(onehot, logits, 0.0).sum(-1), onehot


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _xent(hidden, head, targets, weights, ignore_id, chunk, logit_scale):
    """``(loss, denom)`` of padded inputs — with ``weights`` ``(loss, denom,
    row losses)`` — ``chunk`` positions a scan step.
    Evaluation is the forward rule's loss: the gradients beside it feed
    nothing there, and a jitted program keeps one product a chunk (read
    from the loss-only program compiled for the CPU and for a v5e). Called
    outside ``jit`` the scan is a program of its own and runs whole."""
    return _xent_fwd(hidden, head, targets, weights, ignore_id, chunk,
                     logit_scale)[0]


def _xent_fwd(hidden, head, targets, weights, ignore_id, chunk, logit_scale):
    # unmasked positions, counted before the scan (at least 1)
    denom = jnp.maximum((targets != ignore_id).sum().astype(jnp.float32), 1.0)
    operand = jnp.result_type(hidden, head)

    def body(carry, i):
        total, d_head = carry
        h = lax.dynamic_slice_in_dim(hidden, i * chunk, chunk, 1)
        t = lax.dynamic_slice_in_dim(targets, i * chunk, chunk, 1)
        mask = (t != ignore_id).astype(jnp.float32)
        if weights is not None:
            # a row's weight multiplies its loss and so its gradients: it
            # rides where the mask does
            mask = mask * lax.dynamic_slice_in_dim(weights, i * chunk, chunk, 1)
        logits = _logits(h, head, logit_scale)
        lse, nll, onehot = _nll(logits, t)
        # one expression, in the dtype the MXU takes it, for both products
        # (XLA fuses it into each product's operand: no copy is written)
        dlogits = ((jnp.exp(logits - lse[..., None]) - onehot)
                   * (mask * (logit_scale / denom))[..., None]).astype(operand)
        d_h = lax.dot_general(
            dlogits, head, dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        d_head = (d_head + lax.dot_general(
            dlogits, h, dimension_numbers=(((0, 1), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32)).astype(head.dtype)
        total = total + (nll * mask).sum()
        if weights is None:
            return (total, d_head), d_h.astype(hidden.dtype)
        # the row's own loss, unweighted: what the caller reports a head
        # by, and (over the count) the gradient of its weight
        return (total, d_head), (d_h.astype(hidden.dtype),
                                 jnp.where(t != ignore_id, nll, 0.0))

    (total, d_head), per_chunk = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros_like(head)),
        jnp.arange(hidden.shape[1] // chunk))
    d_hidden, rows = per_chunk if weights is not None else (per_chunk, None)
    # [n, B, C, D] -> [B, n * C, D]
    d_hidden = jnp.moveaxis(d_hidden, 0, 1).reshape(hidden.shape)
    if weights is None:
        return (total / denom, denom), (d_hidden, d_head)
    rows = jnp.moveaxis(rows, 0, 1).reshape(targets.shape)
    return (total / denom, denom, rows), (d_hidden, d_head, rows / denom)


def _xent_bwd(ignore_id, chunk, logit_scale, residuals, cotangents):
    d_hidden, d_head, *d_weights = residuals
    # the count of unmasked positions carries no gradient, nor do the row
    # losses handed back beside the loss: they are a report
    g = cotangents[0]
    return ((g * d_hidden).astype(d_hidden.dtype),
            (g * d_head).astype(d_head.dtype), None,
            g * d_weights[0] if d_weights else None)


_xent.defvjp(_xent_fwd, _xent_bwd)


def fused_softmax_xent(
    hidden: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    *,
    weights: jax.Array | None = None,
    ignore_id: int = -1,
    chunk_size: int | None = None,
    logit_scale: float = 1.0,
):
    """Mean next-token cross-entropy from final hidden states.

    Args:
      hidden: ``[B, S, D]`` final (post-LN) hidden states, any float dtype.
      head: ``[V, D]`` output head in *embedding layout* (the tied-head
        ``tok_emb.embedding``; ``kernel.T`` of an untied ``[D, V]``
        head).
      targets: ``[B, S]`` int token ids; positions equal to ``ignore_id``
        contribute nothing to loss or denominator.
      weights: None, or ``[B, S]`` float32: the loss becomes ``sum(weights *
        row losses) / count``, still one pass over each chunk's logits, and
        is differentiable in ``weights`` too (a row's gradient is its loss
        over the count). Several heads on one set of head weights are ONE
        call on their states joined along the sequence, each row weighted
        by its head's share (``models/lm.py looplm_objective``).
      chunk_size: sequence positions per scan step; peak memory is
        ``B · chunk_size · V`` f32. None sizes the chunk in rows from the
        shapes (:func:`chunk_positions`).
      logit_scale: the logits are ``logit_scale * hidden @ head^T`` (a
        model's ``1 / logits_scaling``), applied to the f32 products.

    Returns:
      ``(loss, denom)`` — mean f32 loss over unmasked positions and the
      (f32) count of them, matching ``models.lm.lm_loss``'s contract;
      ``denom`` carries no gradient. With ``weights``: ``(loss, denom, row
      losses)``, the last ``[B, S]`` float32, each row's own unweighted
      loss (0 where masked), a report that carries no gradient either.
    """
    if hidden.ndim != 3:
        raise ValueError(f"hidden must be [B,S,D], got {hidden.shape}")
    if head.ndim != 2 or head.shape[1] != hidden.shape[2]:
        raise ValueError(
            f"head must be [V,D] with D={hidden.shape[2]}, got {head.shape}"
        )
    batch, seq, _ = hidden.shape
    if chunk_size is None:
        chunk_size = chunk_positions(batch, seq, head.shape[0])
    chunk_size = min(chunk_size, seq)
    pad = (-seq) % chunk_size
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=ignore_id)
        if weights is not None:
            weights = jnp.pad(weights, ((0, 0), (0, pad)))
    log_once(log, f"lm head: fused one-pass, [{batch}, {seq}, "
                  f"{head.shape[0]}] logits in {(seq + pad) // chunk_size} "
                  f"chunk(s) of {local_batch(batch) * chunk_size} rows a "
                  f"device, head gradient carried in {head.dtype}")
    if weights is None:
        return _xent(hidden, head, targets, None, ignore_id, chunk_size,
                     float(logit_scale))
    loss, denom, rows = _xent(hidden, head, targets,
                              weights.astype(jnp.float32), ignore_id,
                              chunk_size, float(logit_scale))
    return loss, denom, rows[:, :seq]

"""Chunked, fused softmax cross-entropy for large-vocab LM heads.

The naive LM loss materializes the full logits tensor ``[B, S, V]`` in f32
(GPT-2 345M at microbatch 8, seq 1024: 8·1024·50304·4B ≈ 1.6 GB — the
compile-time OOM recorded in bench.py's r2 evidence, which capped the
microbatch at 8 and MFU at ~0.50). This op never builds it: the head matmul,
log-sum-exp and target-pick run chunk-by-chunk over the sequence inside a
``lax.scan`` whose body is ``jax.checkpoint``-ed, so

- forward peak is one ``[B, chunk, V]`` f32 buffer instead of ``[B, S, V]``;
- backward *recomputes* each chunk's logits from the (bf16) hidden states
  and head — without the checkpoint, scan would stash every chunk's logits
  as residuals and the memory win would vanish;
- the matmul itself runs in the input dtype (bf16 on TPU) with f32
  accumulation via ``preferred_element_type`` — MXU-native, no f32 copy of
  activations or head.

Numerics are identical to ``optax.softmax_cross_entropy_with_integer_labels``
(loss = lse(logits) − logits[target], f32 accumulation throughout); the op
is differentiable w.r.t. both ``hidden`` and ``head``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def fused_softmax_xent(
    hidden: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    *,
    ignore_id: int = -1,
    chunk_size: int = 128,
    logit_scale: float = 1.0,
):
    """Mean next-token cross-entropy from final hidden states.

    Args:
      hidden: ``[B, S, D]`` final (post-LN) hidden states, any float dtype.
      head: ``[V, D]`` output head in *embedding layout* (the tied-head
        ``tok_emb.embedding``; pass ``kernel.T`` for an untied ``[D, V]``
        head).
      targets: ``[B, S]`` int token ids; positions equal to ``ignore_id``
        contribute nothing to loss or denominator.
      chunk_size: sequence positions per scan step. Peak memory is
        ``B · chunk_size · V`` f32; 128 ≈ 1/8 the naive peak at seq 1024.
      logit_scale: the logits are ``logit_scale * hidden @ head^T`` (a
        model's ``1 / logits_scaling``), applied to the f32 products.

    Returns:
      ``(loss, denom)`` — mean f32 loss over unmasked positions and the
      (f32) count of them, matching ``models.gpt.lm_loss``'s contract.
    """
    if hidden.ndim != 3:
        raise ValueError(f"hidden must be [B,S,D], got {hidden.shape}")
    if head.ndim != 2 or head.shape[1] != hidden.shape[2]:
        raise ValueError(
            f"head must be [V,D] with D={hidden.shape[2]}, got {head.shape}"
        )
    seq = hidden.shape[1]
    chunk_size = min(chunk_size, seq)
    pad = (-seq) % chunk_size
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=ignore_id)
    n_chunks = hidden.shape[1] // chunk_size

    def body(carry, i):
        h = lax.dynamic_slice_in_dim(hidden, i * chunk_size, chunk_size, 1)
        t = lax.dynamic_slice_in_dim(targets, i * chunk_size, chunk_size, 1)
        mask = (t != ignore_id).astype(jnp.float32)
        t_safe = jnp.maximum(t, 0)
        # [B, C, V] — f32 accumulation on the MXU, inputs stay bf16
        logits = lax.dot_general(
            h, head,
            dimension_numbers=(((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if logit_scale != 1.0:
            logits = logits * logit_scale
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_safe[..., None], axis=-1)[..., 0]
        total, count = carry
        total = total + ((lse - tgt) * mask).sum()
        count = count + mask.sum()
        return (total, count), None

    # checkpoint: scan must NOT keep each chunk's logits as bwd residuals
    (total, denom), _ = lax.scan(
        jax.checkpoint(body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(n_chunks),
    )
    denom = jnp.maximum(denom, 1.0)
    return total / denom, denom

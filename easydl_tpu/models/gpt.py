"""GPT family — BASELINE config 4 ("GPT-2 345M data-parallel, Brain-driven
autoscale 8→32 chips"). The flagship model for the driver's entry point.

Sizes follow the GPT-2 paper naming; "345m" (a.k.a. GPT-2 medium:
24 layers, d_model 1024, 16 heads) is the benchmark config. Vocab is padded
to a multiple of 128 so the embedding/logits matmuls tile cleanly on the MXU.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from easydl_tpu.core.data import SyntheticTokens
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import Transformer, TransformerConfig
from easydl_tpu.ops.fused_xent import fused_softmax_xent, local_batch
from easydl_tpu.utils.logging import get_logger, log_once

#: name -> (n_layers, d_model, n_heads)
SIZES: Dict[str, Tuple[int, int, int]] = {
    "124m": (12, 768, 12),
    "345m": (24, 1024, 16),
    "762m": (36, 1280, 20),
    "1558m": (48, 1600, 25),
    # tiny sizes for tests/dryruns
    "test": (2, 128, 4),
}


def lm_loss(logits, targets, ignore_id: int = -1):
    """Mean next-token cross-entropy (fp32 accumulation)."""
    logits = logits.astype(jnp.float32)
    mask = (targets != ignore_id).astype(jnp.float32)
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(targets, 0)
    )
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (losses * mask).sum() / denom
    return loss, denom


#: The chunked fused head (ops/fused_xent.py) takes the place of full logits
#: when ONE device's share of a microbatch's ``[B, S, V]`` float32 logits
#: would pass this: an eighth of a v5e chip's 16 GB. It is chosen for the
#: room: GPT-2 at 8 x 1024 x 50304 (1.5 GiB) keeps full logits, the Granite
#: hybrid at 2 x 4096 x 100352 (3.1 GiB, beside 1.5 GiB of bf16 logits) does
#: not fit them. The fused head forms the loss and both gradients in one
#: pass over each chunk's logits; whether it also beats full logits where
#: both fit has not been measured since it stopped recomputing them. Which
#: head a shape gets is decided here and nowhere else; how the fused head
#: cuts the sequence into chunks is its own matter
#: (``fused_xent.chunk_positions``).
FUSED_HEAD_LOGITS_BYTES = 2 * 1024 ** 3

log = get_logger("models", "gpt")


def fused_head_by_shape(batch: int, seq: int, vocab: int) -> bool:
    """The rule above, for logits of ``[batch, seq, vocab]`` as the loss
    function sees them under the context mesh (the one ``Trainer`` enters):
    the batch is split over the mesh's batch axes where it divides."""
    return 4 * local_batch(batch) * seq * vocab > FUSED_HEAD_LOGITS_BYTES


def lm_bundle(cfg: TransformerConfig, name: str, *,
              moe_aux_weight: float = 0.01) -> ModelBundle:
    """The causal-LM bundle of one description of the stack: init, loss
    (full logits, or the fused chunked head where
    :func:`fused_head_by_shape` says so), eval, data and the hints."""
    model = Transformer(cfg)
    seq_len, vocab, n_layers = cfg.max_seq, cfg.vocab, cfg.n_layers

    def init_fn(rng):
        tokens = jnp.zeros((1, seq_len), jnp.int32)
        return model.init(rng, tokens)["params"]

    def _lm_loss_from(params, batch, mutable=False):
        """LM loss via the fused chunked head or full logits.

        The fused path asks the stack for hidden states and applies the tied
        head chunk-by-chunk (ops/fused_xent.py) — the full [B,S,V] f32
        logits buffer never exists.
        """
        mut = None
        if cfg.tied_head and fused_head_by_shape(*batch["inputs"].shape,
                                                 vocab):
            out = model.apply(
                {"params": params}, batch["inputs"], return_hidden=True,
                **({"mutable": ["intermediates"]} if mutable else {}),
            )
            hidden = out[0] if mutable else out
            mut = out[1] if mutable else None
            head = params["tok_emb"]["embedding"]
            if hasattr(head, "unbox"):  # boxed (LogicallyPartitioned) params
                head = head.unbox()
            # Cast the stored-f32 param to the compute dtype — exactly what
            # tok_emb.attend's dtype promotion does on the logits path. A
            # bf16×f32 dot_general promotes to an f32 matmul, which would
            # take the [B,chunk,V] matmul off the bf16 MXU path.
            head = jnp.asarray(head, dtype=hidden.dtype)
            with jax.named_scope("lm_head_loss"):
                loss, _ = fused_softmax_xent(
                    hidden, head, batch["targets"],
                    logit_scale=1.0 / cfg.logits_scaling,
                )
        else:
            log_once(log, f"lm head: full logits "
                          f"{[*batch['inputs'].shape, vocab]} in float32")
            out = model.apply(
                {"params": params}, batch["inputs"],
                **({"mutable": ["intermediates"]} if mutable else {}),
            )
            logits = out[0] if mutable else out
            mut = out[1] if mutable else None
            with jax.named_scope("loss"):
                loss, _ = lm_loss(logits, batch["targets"])
        return loss, mut

    def loss_fn(params, batch, rng):
        if cfg.moe_experts:
            loss, mut = _lm_loss_from(params, batch, mutable=True)
            aux = jnp.sum(
                jnp.asarray(mut["intermediates"]["moe_aux_loss"][0])
            )
            return loss + moe_aux_weight * aux, {
                "perplexity": jnp.exp(loss),
                "moe_balance": aux / max(n_layers, 1),
            }
        loss, _ = _lm_loss_from(params, batch)
        return loss, {"perplexity": jnp.exp(loss)}

    def eval_fn(params, batch, rng):
        # Pure LM loss — no balance regularizer, so eval is comparable
        # across dense/MoE configs and aux weights.
        loss, _ = _lm_loss_from(params, batch)
        return loss, {"perplexity": jnp.exp(loss)}

    def make_data(global_batch: int, seed: int = 0):
        return SyntheticTokens(global_batch, seq_len=seq_len, vocab=vocab, seed=seed)

    return ModelBundle(
        name=name,
        init_fn=init_fn,
        loss_fn=loss_fn,
        make_data=make_data,
        eval_fn=eval_fn,
        param_count_hint=cfg.param_count,
        # the description's own count: a layer without a score matrix adds
        # no 12 d s (core/mfu.py's GPT formula is this for all-attention)
        flops_per_sample_hint=cfg.train_flops_per_token(seq_len) * seq_len,
    )


@register_model("gpt")
def make_gpt(
    size: str = "345m",
    seq_len: int = 1024,
    vocab: int = 50304,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    attention_fn=None,
    dropout: float = 0.0,
    dtype: str = "float32",
    moe_experts: int = 0,
    moe_k: int = 2,
    moe_aux_weight: float = 0.01,
    moe_capacity_factor: float = 1.25,
    pipeline_fn=None,
    pipeline_stages: int = 0,
) -> ModelBundle:
    n_layers, d_model, n_heads = SIZES[size]
    cfg = TransformerConfig(
        vocab=vocab,
        d_model=d_model,
        n_heads=n_heads,
        n_layers=n_layers,
        d_ff=4 * d_model,
        max_seq=seq_len,
        causal=True,
        dropout=dropout,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        attention_fn=attention_fn,
        dtype=dtype,
        tied_head=True,
        moe_experts=moe_experts,
        moe_k=moe_k,
        moe_capacity_factor=moe_capacity_factor,
        pipeline_fn=pipeline_fn,
        pipeline_stages=pipeline_stages,
    )
    return lm_bundle(
        cfg, f"gpt-{size}" + (f"-moe{moe_experts}" if moe_experts else ""),
        moe_aux_weight=moe_aux_weight)


@register_model("gpt_moe")
def make_gpt_moe(**kwargs) -> ModelBundle:
    """GPT with mixture-of-experts FFNs (experts shard over ``ep``)."""
    kwargs.setdefault("moe_experts", 8)
    return make_gpt(**kwargs)

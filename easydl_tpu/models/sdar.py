"""SDAR (JetLM; HF model type ``sdar_moe``; arXiv:2510.06303): an
autoregressive fine-grained mixture-of-experts decoder converted to BLOCK
DIFFUSION by continued training (BD3-LM's objective, arXiv:2503.09573). As a
plain language model it is Mellum 2's sibling — every layer sparse, a linear
softmax router over all experts, the top ``k`` renormalised
(``norm_topk_prob``), small SwiGLU experts and nothing shared; GQA 32 over 4
heads of 128, rotary over the whole head, pre-norm RMSNorm, no biases, untied
head — with an RMSNorm over each head's dimensions on q and k inside the
rotary kernel (``AttentionKind.qk_norm``). What it is TRAINED by differs in
kind: a step runs each sequence as ``[noised || clean]``, twice its tokens in
rows, under a mask by blocks that is neither causal nor a window
(``ops/flash_attention.py BlockDiffusion``), both halves at the positions
``0 .. L - 1``, and the loss is the masked tokens' cross-entropy weighted ``1 /
t`` on the noised half alone (``models/lm.py block_diffusion_objective``;
``TransformerConfig.block_diffusion``). One description of
``models/transformer.py``'s stack; nothing here but the published numbers,
the block length, and Mellum 2's one start of its own: the embedding table
at unit scale (``embedding_init_std``) — the same reason holds, and here
doubly: every masked row carries ONE embedding row, and beside 0.02
embeddings the routers' loads follow the seed.

``size="30b-a3b-chat"`` is SDAR-30B-A3B-Chat as published
(huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``config.json``): 48 identical
layers, 2048 wide, 128 experts of 768, top-8. A chip runs a share of it:
``layer_types`` states the depth in the published vocabulary
(``full_attention`` is the one kind), ``experts_held`` the contiguous range of
routed experts this chip holds of each layer (the router keeps its published
width), ``vocab`` its slice of the vocabulary, whose LAST row stands for the
mask token. ``block_length`` is the family's released Chat checkpoints' and
its generation script's default, 4 (``config.json`` does not give it).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (AttentionKind, MoeConfig,
                                           RopeScheme, TransformerConfig)
from easydl_tpu.ops.moe import ROUTERS

_KIND = "full_attention"

#: name -> widths; keys as the published ``config.json`` has them
SIZES: Dict[str, Dict[str, Any]] = {
    "30b-a3b-chat": dict(
        hidden_size=2048, head_dim=128, num_attention_heads=32,
        num_key_value_heads=4, num_experts=128, num_experts_per_tok=8,
        moe_intermediate_size=768, norm_topk_prob=True, rope_theta=1000000.0,
        rms_norm_eps=1e-6, num_hidden_layers=48),
    # tiny, for tests and dry runs: every mechanism — 4 query heads over 2
    # key/value heads, the q/k norm, a block length SMALLER than a test's
    # kernel block, a softmax router over 16 experts top-4, nothing shared
    "test": dict(
        hidden_size=64, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, num_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, norm_topk_prob=True, rope_theta=1000000.0,
        rms_norm_eps=1e-6, num_hidden_layers=3),
}


def describe(
    size: str = "30b-a3b-chat",
    seq_len: int = 8192,
    vocab: int = 151936,
    block_length: int = 4,
    layer_types: Optional[Sequence[str]] = None,
    experts_held: Optional[Tuple[int, int]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of an SDAR of ``size`` trained by block
    diffusion at ``seq_len`` tokens (``2 x seq_len`` rows a sequence)."""
    w = SIZES[size]
    kinds = tuple(layer_types or (_KIND,) * w["num_hidden_layers"])
    if set(kinds) != {_KIND}:
        raise ValueError(f"SDAR's layers are all {_KIND!r} with a sparse "
                         f"FFN; got {kinds}")
    lo, hi = experts_held or (0, w["num_experts"])
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        n_kv_heads=w["num_key_value_heads"],
        head_size=w["head_dim"],
        n_layers=len(kinds),
        d_ff=0,  # no dense layer (`intermediate_size` names one no layer has)
        max_seq=seq_len,
        causal=False,  # the block mask is the attention's only mask
        block_diffusion=block_length,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=False,
        layers=tuple((_KIND, "moe") for _ in kinds),
        norm="rmsnorm",
        norm_eps=w["rms_norm_eps"],
        position="none",  # the attention kind brings its rotary scheme
        bias=False,
        # a stand-in for a trained model's token-specific stream, not a
        # published number (``TransformerConfig.embedding_init_std``)
        embedding_init_std=1.0,
        attention_kinds=((_KIND, AttentionKind(
            rope=RopeScheme(theta=float(w["rope_theta"])), qk_norm=True)),),
        moe=MoeConfig(
            experts_total=w["num_experts"], experts_held=(int(lo), int(hi)),
            k=w["num_experts_per_tok"], d_ff=w["moe_intermediate_size"],
            router=ROUTERS[2]),  # ``norm_topk_prob``: the renormalised form
    )


@register_model("sdar")
def make_sdar(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The objective is
    block diffusion's (``models/lm.py lm_bundle`` under
    ``cfg.block_diffusion``): a batch's ``inputs`` are the clean tokens, the
    noise is drawn inside the loss from the step's key."""
    cfg = describe(**description)
    size = description.get("size", "30b-a3b-chat")
    lo, hi = cfg.moe.experts_held
    return lm_bundle(cfg, f"sdar-{size}-{cfg.n_layers}l-e{lo}-{hi}"
                          f"-b{cfg.block_diffusion}")

"""Kimi-Linear-48B-A3B (moonshotai; HF model type ``kimi_linear``, "48B-A3B";
the Kimi Linear report, arXiv:2510.26692): a hybrid of linear and full
attention, three layers in four Kimi Delta Attention (KDA: a delta rule whose
state forgets at a rate of its own in every key channel; q, k and v each
through its own causal convolution of ``short_conv_kernel_size`` taps and a
SiLU, q and k L2-normed a head, a step size a head, the result normed a head
and gated by a sigmoid: ``ops/kda.py``), the fourth multi-head latent
attention WITHOUT positions (``mla_use_nope``: DeepSeek-V3's keys with
``q_lora_rank`` null — q straight from the model's width — and no rotation
of any lane; the 64-wide key part shared by the heads is kept). No positions
anywhere in the model. ``first_k_dense_replace`` leading dense SwiGLU layers,
then fine-grained mixture-of-experts layers: a sigmoid router over all
experts that selects by its scores plus a per-expert bias which takes no
gradient (``num_expert_group`` = ``topk_group`` = 1: no grouping), top-k
renormalised and scaled, shared experts. Pre-norm RMSNorm, untied head. One
description of ``models/transformer.py``'s stack; nothing here but the
published numbers.

``size="48b-a3b"`` is Kimi-Linear-48B-A3B-Instruct as published
(huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct, ``config.json``): 27
layers, 2304 wide, 32 KDA heads of 128 with convolutions of 4, 32 latent
heads of 128 + 64 against 128 up from a latent of 512, dense SwiGLU 9216,
256 experts of 1024, top-8 times 2.446, one shared expert, 163,840-row
vocabulary. A chip runs a share of it: ``layer_types`` states the depth
(``kda_dense`` | ``kda_sparse`` | ``mla_sparse`` | ``mla_dense``, the published
``kda_layers`` / ``full_attn_layers`` / ``first_k_dense_replace`` written
out), ``experts_held`` the contiguous range of routed experts this chip
holds of each layer (the router keeps its published width), ``vocab`` its
slice of the vocabulary.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (AttentionKind, KdaConfig, LowRank,
                                           MoeConfig, TransformerConfig)

#: the latent attention kind's name in a description's ``layers``
MLA = "latent_attention"
#: a layer type's mixer and FFN
LAYER_TYPES = {"kda_dense": ("kda", "swiglu"), "kda_sparse": ("kda", "moe"),
               "mla_dense": (MLA, "swiglu"), "mla_sparse": (MLA, "moe")}

#: name -> widths; keys as the published ``config.json`` has them
#: (``linear_attn_config``'s with its prefix ``kda_``)
SIZES: Dict[str, Dict[str, Any]] = {
    "48b-a3b": dict(
        hidden_size=2304, num_attention_heads=32, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kda_num_heads=32, kda_head_dim=128, kda_short_conv_kernel_size=4,
        full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
        intermediate_size=9216, moe_intermediate_size=1024, num_experts=256,
        num_experts_per_token=8, num_shared_experts=1,
        routed_scaling_factor=2.446, first_k_dense_replace=1,
        num_hidden_layers=27, rms_norm_eps=1e-5),
    # tiny, for tests and dry runs: every mechanism — KDA heads of 16 with
    # convolutions of 4, latent heads of 16 + 8 against 16 without a
    # bottleneck, 32 experts top-4 and a shared one, a leading dense layer,
    # the published three-to-one among the layers behind it
    "test": dict(
        hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kda_num_heads=4, kda_head_dim=16, kda_short_conv_kernel_size=4,
        full_attn_layers=(4,),
        intermediate_size=128, moe_intermediate_size=32, num_experts=32,
        num_experts_per_token=4, num_shared_experts=1,
        routed_scaling_factor=2.446, first_k_dense_replace=1,
        num_hidden_layers=5, rms_norm_eps=1e-5),
}


def published_layer_types(size: str) -> Tuple[str, ...]:
    """``layer_types`` as the published keys write them out: layer ``i``
    (from 1) is latent attention where ``full_attn_layers`` names it and KDA
    elsewhere, dense up to ``first_k_dense_replace`` and sparse behind."""
    w = SIZES[size]
    return tuple(
        f"{'mla' if i in w['full_attn_layers'] else 'kda'}_"
        f"{'dense' if i <= w['first_k_dense_replace'] else 'sparse'}"
        for i in range(1, w["num_hidden_layers"] + 1))


def describe(
    size: str = "48b-a3b",
    seq_len: int = 16384,
    vocab: int = 163840,
    layer_types: Optional[Sequence[str]] = None,
    experts_held: Optional[Tuple[int, int]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of a Kimi Linear model of ``size``."""
    w = SIZES[size]
    kinds = tuple(layer_types or published_layer_types(size))
    if not set(kinds) <= set(LAYER_TYPES):
        raise ValueError(f"Kimi Linear's layers are {sorted(LAYER_TYPES)}; "
                         f"got {kinds}")
    lo, hi = experts_held or (0, w["num_experts"])
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        head_size=w["qk_nope_head_dim"] + w["qk_rope_head_dim"],
        n_layers=len(kinds),
        d_ff=w["intermediate_size"],
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=False,
        layers=tuple(LAYER_TYPES[kind] for kind in kinds),
        norm="rmsnorm",
        norm_eps=w["rms_norm_eps"],
        position="none",  # no positions anywhere: the recurrence orders
        bias=False,
        kda=KdaConfig(n_heads=w["kda_num_heads"], head_dim=w["kda_head_dim"],
                      d_conv=w["kda_short_conv_kernel_size"]),
        # no bottleneck on q (q_lora_rank null) and no rotary scheme (NoPE)
        attention_kinds=((MLA, AttentionKind(lowrank=LowRank(
            q_rank=None, kv_rank=w["kv_lora_rank"],
            nope_dim=w["qk_nope_head_dim"], rope_dim=w["qk_rope_head_dim"],
            value_dim=w["v_head_dim"]))),),
        moe=MoeConfig(
            experts_total=w["num_experts"],
            experts_held=(int(lo), int(hi)), k=w["num_experts_per_token"],
            d_ff=w["moe_intermediate_size"],
            shared_d_ff=w["num_shared_experts"] * w["moe_intermediate_size"],
            scaling=w["routed_scaling_factor"], selection_bias=True),
    )


@register_model("kimi_linear")
def make_kimi_linear(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`."""
    cfg = describe(**description)
    size = description.get("size", "48b-a3b")
    lo, hi = cfg.moe.experts_held
    return lm_bundle(cfg, f"kimi-linear-{size}-{cfg.n_layers}l-e{lo}-{hi}")

"""JoyAI-LLM-Flash (JD; HF model type ``joyai_llm_flash``, "48B-A2.7B"): a
DeepSeek-V3-shaped decoder (arXiv:2412.19437, sections 2.1 and 2.2, whose
``config.json`` keys these are). Every layer's attention is multi-head latent
attention (MLA): q through a rank-``q_lora_rank`` bottleneck with an RMSNorm
inside it, k and v up from one shared rank-``kv_lora_rank`` latent with an
RMSNorm of its own, a head scoring with ``qk_nope_head_dim`` dimensions without
positions beside ``qk_rope_head_dim`` rotated ones (interleaved pairing,
``rope_interleave``; the rotated key part ONE vector a token that all heads
share), values ``v_head_dim`` wide. ``first_k_dense_replace`` leading dense
SwiGLU layers, then fine-grained mixture-of-experts layers: a sigmoid router
over all experts that selects by its scores plus a per-expert bias which takes
no gradient and weighs by the scores without it (``noaux_tc``; ``n_group`` =
``topk_group`` = 1: no grouping), top-k renormalised and scaled, shared
experts. ``num_nextn_predict_layers`` = 1: a multi-token-prediction module
behind the stack (the main state and the next token's embedding, normed and
joined, one more sparse layer, the main model's head). Pre-norm RMSNorm,
untied head. One description of ``models/transformer.py``'s stack; nothing
here but the published numbers.

``size="llm-flash"`` is JoyAI-LLM-Flash as published (huggingface.co/
jdopensource/JoyAI-LLM-Flash, ``config.json``): 40 layers, 2048 wide, 32
heads of 128 + 64 against 128, latents of 1536 and 512, dense SwiGLU 7168, 256
experts of 768, top-8, one shared expert, 129,280-row vocabulary. A chip runs
a share of it: ``layer_types`` states the depth (``dense`` | ``sparse``, the
published ``first_k_dense_replace`` / ``moe_layer_freq`` written out),
``experts_held`` the contiguous range of routed experts this chip holds of
each layer (the router keeps its published width), ``vocab`` its slice of
the vocabulary, ``mtp`` whether this chip holds the module (in a pipeline it
lies with the head).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (AttentionKind, LowRank, MoeConfig,
                                           MtpConfig, RopeScheme,
                                           TransformerConfig)

#: the one attention kind's name in a description's ``layers``
MLA = "latent_attention"

#: name -> widths; keys as the published ``config.json`` has them
SIZES: Dict[str, Dict[str, Any]] = {
    "llm-flash": dict(
        hidden_size=2048, num_attention_heads=32, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=32000000.0, rope_interleave=True,
        intermediate_size=7168, moe_intermediate_size=768,
        n_routed_experts=256, num_experts_per_tok=8, n_shared_experts=1,
        routed_scaling_factor=2.5, first_k_dense_replace=1,
        num_hidden_layers=40, num_nextn_predict_layers=1,
        rms_norm_eps=1e-6),
    # tiny, for tests and dry runs: every mechanism — ranks under the model's
    # width, heads of 16 + 8 against 16 (a score size that is one and a half
    # times the value's, as 192 is of 128), 32 experts top-4 and a shared
    # one, a leading dense layer, the module
    "test": dict(
        hidden_size=64, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=32000000.0, rope_interleave=True,
        intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=32, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=2.5, first_k_dense_replace=1,
        num_hidden_layers=3, num_nextn_predict_layers=1,
        rms_norm_eps=1e-6),
}


def describe(
    size: str = "llm-flash",
    seq_len: int = 8192,
    vocab: int = 129280,
    layer_types: Optional[Sequence[str]] = None,
    experts_held: Optional[Tuple[int, int]] = None,
    mtp: bool = True,
    mtp_weight: float = 0.3,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of a JoyAI-LLM of ``size``. ``mtp_weight`` is
    the second objective's lambda (DeepSeek-V3's first phase: 0.3; not in
    ``config.json``)."""
    w = SIZES[size]
    dense = w["first_k_dense_replace"]
    kinds = tuple(layer_types or ("dense",) * dense
                  + ("sparse",) * (w["num_hidden_layers"] - dense))
    if not set(kinds) <= {"dense", "sparse"}:
        raise ValueError(f"JoyAI-LLM's layers are 'dense' | 'sparse'; got "
                         f"{kinds}")
    if mtp and w["num_nextn_predict_layers"] != 1:
        raise ValueError("the multi-token-prediction module has depth 1")
    lo, hi = experts_held or (0, w["n_routed_experts"])
    head_dim = w["qk_nope_head_dim"] + w["qk_rope_head_dim"]
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        head_size=head_dim,
        n_layers=len(kinds),
        d_ff=w["intermediate_size"],
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=False,
        layers=tuple((MLA, "moe" if kind == "sparse" else "swiglu")
                     for kind in kinds),
        norm="rmsnorm",
        norm_eps=w["rms_norm_eps"],
        position="none",  # the attention kind brings its rotary scheme
        bias=False,
        attention_kinds=((MLA, AttentionKind(
            rope=RopeScheme(theta=float(w["rope_theta"]),
                            rotary_dim=w["qk_rope_head_dim"],
                            interleaved=bool(w["rope_interleave"]),
                            last=True),
            lowrank=LowRank(
                q_rank=w["q_lora_rank"], kv_rank=w["kv_lora_rank"],
                nope_dim=w["qk_nope_head_dim"],
                rope_dim=w["qk_rope_head_dim"],
                value_dim=w["v_head_dim"]))),),
        moe=MoeConfig(
            experts_total=w["n_routed_experts"],
            experts_held=(int(lo), int(hi)), k=w["num_experts_per_tok"],
            d_ff=w["moe_intermediate_size"],
            shared_d_ff=w["n_shared_experts"] * w["moe_intermediate_size"],
            scaling=w["routed_scaling_factor"], selection_bias=True),
        mtp=MtpConfig(mixer=MLA, ffn="moe", weight=mtp_weight)
        if mtp else None,
    )


@register_model("joyai")
def make_joyai(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The two heads are
    one call of the fused chunked head (``models/lm.py mtp_objective``)."""
    cfg = describe(**description)
    size = description.get("size", "llm-flash")
    lo, hi = cfg.moe.experts_held
    return lm_bundle(cfg, f"joyai-{size}-{cfg.n_layers}l"
                          f"{'+mtp' if cfg.mtp else ''}-e{lo}-{hi}")

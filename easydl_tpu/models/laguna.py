"""Laguna (poolside; HF model type ``laguna``): a decoder that mixes
window-512 and full attention layers, each kind with its own query-head count
over shared key/value heads, its own rotary scheme (the window layers the
default one over the whole head, the full layers YaRN over half of it) and a
per-head sigmoid gate on the attention output; a leading dense SwiGLU layer,
then fine-grained mixture-of-experts layers (sigmoid router over all experts,
top-k renormalised and scaled, a shared expert); pre-norm RMSNorm, untied
head. One description of ``models/transformer.py``'s stack; nothing here but
the published numbers.

``size="xs.2"`` is Laguna-XS.2 as published (huggingface.co/poolside/
Laguna-XS.2, ``config.json``): 40 layers, 2048 wide, heads of 128 (48 in
full layers, 64 in window layers, over 8 key/value heads), 256 experts of
512, top-8, one shared expert of 512, 33.4B parameters. A chip runs a share
of it: ``layer_types`` / ``mlp_layer_types`` / ``heads_per_layer`` state the
depth in the published vocabulary, ``experts_held`` the contiguous range of
routed experts this chip holds of each layer (the router keeps its published
width), ``vocab`` its slice of the vocabulary.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (AttentionKind, MoeConfig,
                                           RopeScheme, TransformerConfig)

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3

#: name -> widths; keys as the published ``config.json`` has them
SIZES: Dict[str, Dict[str, Any]] = {
    "xs.2": dict(
        hidden_size=2048, head_dim=128, num_key_value_heads=8,
        intermediate_size=8192, num_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        moe_routed_scaling_factor=2.5, sliding_window=512,
        heads={"full_attention": 48, "sliding_attention": 64},
        rope_parameters={
            "full_attention": dict(
                rope_theta=500000.0, rope_type="yarn", factor=64.0,
                original_max_position_embeddings=4096, beta_slow=1.0,
                beta_fast=64.0, attention_factor=1.4158883083359672,
                partial_rotary_factor=0.5),
            "sliding_attention": dict(
                rope_theta=10000.0, rope_type="default",
                partial_rotary_factor=1.0)},
        layer_types=_PERIOD * 10,
        mlp_layer_types=("dense",) + ("sparse",) * 39),
    # tiny, for tests and dry runs: every mechanism — two head counts over 2
    # key/value heads (ratios 3 and 4), a window shorter than the sequence,
    # partial YaRN rotary, 16 experts top-2 and a shared one
    "test": dict(
        hidden_size=64, head_dim=16, num_key_value_heads=2,
        intermediate_size=128, num_experts=16, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        moe_routed_scaling_factor=2.5, sliding_window=16,
        heads={"full_attention": 6, "sliding_attention": 8},
        rope_parameters={
            "full_attention": dict(
                rope_theta=500000.0, rope_type="yarn", factor=64.0,
                original_max_position_embeddings=32, beta_slow=1.0,
                beta_fast=4.0, attention_factor=1.4158883083359672,
                partial_rotary_factor=0.5),
            "sliding_attention": dict(
                rope_theta=10000.0, rope_type="default",
                partial_rotary_factor=1.0)},
        layer_types=_PERIOD + ("full_attention",),
        mlp_layer_types=("dense",) + ("sparse",) * 4),
}
_YARN = ("factor", "original_max_position_embeddings", "beta_fast",
         "beta_slow", "attention_factor")


def _rope(head_dim: int, p: Dict[str, Any]) -> RopeScheme:
    rot = int(head_dim * p["partial_rotary_factor"])
    return RopeScheme(
        theta=float(p["rope_theta"]),
        rotary_dim=0 if rot == head_dim else rot,
        yarn=tuple((key, float(p[key])) for key in _YARN)
        if p["rope_type"] == "yarn" else None)


def describe(
    size: str = "xs.2",
    seq_len: int = 8192,
    vocab: int = 100352,
    layer_types: Optional[Sequence[str]] = None,
    mlp_layer_types: Optional[Sequence[str]] = None,
    heads_per_layer: Optional[Sequence[int]] = None,
    experts_held: Optional[Tuple[int, int]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of a Laguna of ``size``. ``heads_per_layer``
    (the published ``num_attention_heads_per_layer``) is checked against the
    kinds' own counts, which it repeats."""
    w = SIZES[size]
    kinds = tuple(layer_types or w["layer_types"])
    ffns = tuple(mlp_layer_types or w["mlp_layer_types"][:len(kinds)])
    if len(ffns) != len(kinds) or not set(ffns) <= {"dense", "sparse"} \
            or not set(kinds) <= set(w["heads"]):
        raise ValueError(f"Laguna's layers are {sorted(w['heads'])} with "
                         f"'dense' | 'sparse' FFNs, one of each a layer; got "
                         f"{kinds} and {ffns}")
    if heads_per_layer is not None and \
            tuple(heads_per_layer) != tuple(w["heads"][k] for k in kinds):
        raise ValueError(f"heads_per_layer {tuple(heads_per_layer)} is not "
                         f"the kinds' own {w['heads']} over {kinds}")
    lo, hi = experts_held or (0, w["num_experts"])
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["heads"]["full_attention"],
        n_kv_heads=w["num_key_value_heads"],
        head_size=w["head_dim"],
        n_layers=len(kinds),
        d_ff=w["intermediate_size"],
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=False,
        layers=tuple((kind, "moe" if ffn == "sparse" else "swiglu")
                     for kind, ffn in zip(kinds, ffns)),
        norm="rmsnorm",
        norm_eps=1e-6,
        position="none",  # each attention kind brings its own rotary scheme
        bias=False,
        attention_kinds=tuple(
            (name, AttentionKind(
                n_heads=heads,
                window=w["sliding_window"] if name == "sliding_attention"
                else 0,
                rope=_rope(w["head_dim"], w["rope_parameters"][name]),
                gate=True))
            for name, heads in w["heads"].items()),
        moe=MoeConfig(
            experts_total=w["num_experts"], experts_held=(int(lo), int(hi)),
            k=w["num_experts_per_tok"], d_ff=w["moe_intermediate_size"],
            shared_d_ff=w["shared_expert_intermediate_size"],
            scaling=w["moe_routed_scaling_factor"]),
    )


@register_model("laguna")
def make_laguna(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The head is the
    fused chunked one wherever full logits would not fit
    (``models/lm.py fused_head_by_shape``)."""
    cfg = describe(**description)
    size = description.get("size", "xs.2")
    lo, hi = cfg.moe.experts_held
    return lm_bundle(cfg, f"laguna-{size}-{cfg.n_layers}l-e{lo}-{hi}")

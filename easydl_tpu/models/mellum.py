"""Mellum 2 (JetBrains; HF model type ``mellum``): a decoder whose every
layer is sparse — a linear softmax router over all experts, the top ``k``
renormalised (``norm_topk_prob``), small SwiGLU experts and nothing shared, no
dense layer — under window-1,024 and full attention layers three to one, both
kinds with the same 32 query heads over 4 key/value heads of 128: the window
layers the default rotary over the whole head, the full layers YaRN over the
whole head with a stated attention factor; pre-norm RMSNorm, no biases, untied
head. One description of ``models/transformer.py``'s stack; nothing here but
the published numbers and one start of its own: the embedding table at unit
scale (``embedding_init_std``), without which the rows that land on a chip's
sixteen experts, and the step's time with them, follow the seed.

``size="2-12b-a2.5b"`` is Mellum2-12B-A2.5B-Instruct as published
(huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, ``config.json``): 28
layers, 2304 wide, 64 experts of 896, top-8, 12.15B parameters of which 2.44B
meet a token. A chip runs a share of it: ``layer_types`` /
``mlp_layer_types`` state the depth in the published vocabulary,
``experts_held`` the contiguous range of routed experts this chip holds of
each layer (the router keeps its published width), ``vocab`` its slice of the
vocabulary.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (AttentionKind, MoeConfig,
                                           RopeScheme, TransformerConfig)
from easydl_tpu.ops.moe import ROUTERS

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

#: name -> widths; keys as the published ``config.json`` has them
SIZES: Dict[str, Dict[str, Any]] = {
    "2-12b-a2.5b": dict(
        hidden_size=2304, head_dim=128, num_attention_heads=32,
        num_key_value_heads=4, num_experts=64, num_experts_per_tok=8,
        moe_intermediate_size=896, norm_topk_prob=True, sliding_window=1024,
        rope_parameters={
            "full_attention": dict(
                rope_type="yarn", rope_theta=500000.0, factor=16.0,
                original_max_position_embeddings=8192, beta_fast=32.0,
                beta_slow=1.0, attention_factor=1.2772588722239782),
            "sliding_attention": dict(
                rope_type="default", rope_theta=500000.0)},
        layer_types=_PERIOD * 7,
        mlp_layer_types=("sparse",) * 28),
    # tiny, for tests and dry runs: every mechanism — 4 query heads over 2
    # key/value heads, a window shorter than the sequence and WIDER than a
    # test's block, whole-head YaRN with a stated attention factor, a
    # softmax router over 16 experts top-4, nothing shared, every layer
    # sparse
    "test": dict(
        hidden_size=64, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, num_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, norm_topk_prob=True, sliding_window=24,
        rope_parameters={
            "full_attention": dict(
                rope_type="yarn", rope_theta=500000.0, factor=16.0,
                original_max_position_embeddings=32, beta_fast=4.0,
                beta_slow=1.0, attention_factor=1.2772588722239782),
            "sliding_attention": dict(
                rope_type="default", rope_theta=500000.0)},
        layer_types=_PERIOD + ("sliding_attention",),
        mlp_layer_types=("sparse",) * 5),
}
_YARN = ("factor", "original_max_position_embeddings", "beta_fast",
         "beta_slow", "attention_factor")


def _rope(p: Dict[str, Any]) -> RopeScheme:
    """One entry of ``rope_parameters``: over the whole head in both kinds."""
    return RopeScheme(
        theta=float(p["rope_theta"]),
        yarn=tuple((key, float(p[key])) for key in _YARN)
        if p["rope_type"] == "yarn" else None)


def describe(
    size: str = "2-12b-a2.5b",
    seq_len: int = 8192,
    vocab: int = 98304,
    layer_types: Optional[Sequence[str]] = None,
    mlp_layer_types: Optional[Sequence[str]] = None,
    experts_held: Optional[Tuple[int, int]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of a Mellum 2 of ``size``."""
    w = SIZES[size]
    kinds = tuple(layer_types or w["layer_types"])
    ffns = tuple(mlp_layer_types or w["mlp_layer_types"][:len(kinds)])
    if len(ffns) != len(kinds) or set(ffns) != {"sparse"} \
            or not set(kinds) <= set(w["rope_parameters"]):
        raise ValueError(f"Mellum 2's layers are "
                         f"{sorted(w['rope_parameters'])}, every one with a "
                         f"'sparse' FFN; got {kinds} and {ffns}")
    lo, hi = experts_held or (0, w["num_experts"])
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        n_kv_heads=w["num_key_value_heads"],
        head_size=w["head_dim"],
        n_layers=len(kinds),
        d_ff=0,  # no dense layer
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=False,
        layers=tuple((kind, "moe") for kind in kinds),
        norm="rmsnorm",
        norm_eps=1e-6,
        position="none",  # each attention kind brings its own rotary scheme
        bias=False,
        # a stand-in for a trained model's token-specific stream, not a
        # published number (``TransformerConfig.embedding_init_std``)
        embedding_init_std=1.0,
        attention_kinds=tuple(
            (name, AttentionKind(
                window=w["sliding_window"] if name == "sliding_attention"
                else 0,
                rope=_rope(w["rope_parameters"][name])))
            for name in ("full_attention", "sliding_attention")),
        moe=MoeConfig(
            experts_total=w["num_experts"], experts_held=(int(lo), int(hi)),
            k=w["num_experts_per_tok"], d_ff=w["moe_intermediate_size"],
            router=ROUTERS[2]),  # ``norm_topk_prob``: the renormalised form
    )


@register_model("mellum")
def make_mellum(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The head is the
    fused chunked one wherever full logits would not fit
    (``models/lm.py fused_head_by_shape``)."""
    cfg = describe(**description)
    size = description.get("size", "2-12b-a2.5b")
    lo, hi = cfg.moe.experts_held
    return lm_bundle(cfg, f"mellum-{size}-{cfg.n_layers}l-e{lo}-{hi}")

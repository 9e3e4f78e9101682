"""ZAYA1 (Zyphra; HF model type ``zaya``; arXiv:2511.17127): a decoder of
identical layers, each a compressed convolutional attention sub-layer (CCA,
arXiv:2510.04476: q, k and v projected DOWN into the heads' latent, two
causal convolutions over the sequence on q and k, the mean of the
un-convolved q and k added back, half of v taken from the previous token,
L2-normed q and k with a learned temperature, rotary on half a head,
grouped-query attention, the way back up) and an expert sub-layer whose
router is an MLP on a down-projection of the normed input — its state added
to the next layer's through a learned gain, all through the depth — that
picks ONE of the experts or a skip choice, weighted by its softmax
probability; every residual add scales and shifts both of its sides; RMSNorm,
tied head. One description of ``models/transformer.py``'s stack; nothing here
but the published numbers.

``size="8b"`` is ZAYA1-8B as published (huggingface.co/Zyphra/ZAYA1-8B,
``config.json``): 40 layers, 2048 wide, 8 query and 2 key/value heads of 128
(a latent of 1024 and 256), 16 experts of 2048, top-1, a router 256 wide with
17 outputs, vocabulary 262,272. A chip runs a share of it: ``layer_types``
states the depth in the published vocabulary, ``experts_held`` the contiguous
range of routed experts this chip holds of each layer (the router keeps its
published width), ``vocab`` its slice of the vocabulary.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (AttentionKind, LatentMix,
                                           MoeConfig, RopeScheme,
                                           TransformerConfig)
from easydl_tpu.ops.moe import ROUTERS

#: name -> widths; keys as the published ``config.json`` has them
SIZES: Dict[str, Dict[str, Any]] = {
    "8b": dict(
        hidden_size=2048, head_dim=128, num_attention_heads=8,
        num_key_value_heads=2, num_experts=16, num_experts_per_tok=1,
        moe_intermediate_size=2048, router_hidden_size=256, cca_time0=2,
        cca_time1=2, rms_norm_eps=1e-5, num_hidden_layers=40,
        rope_parameters={"hybrid": dict(
            rope_theta=5000000.0, rope_type="default",
            partial_rotary_factor=0.5)}),
    # tiny, for tests and dry runs: every mechanism — a latent of half and an
    # eighth of the model (four query heads a key/value head), 16 experts
    # and the skip choice, a router 16 wide
    "test": dict(
        hidden_size=128, head_dim=8, num_attention_heads=8,
        num_key_value_heads=2, num_experts=16, num_experts_per_tok=1,
        moe_intermediate_size=64, router_hidden_size=16, cca_time0=2,
        cca_time1=2, rms_norm_eps=1e-5, num_hidden_layers=4,
        rope_parameters={"hybrid": dict(
            rope_theta=5000000.0, rope_type="default",
            partial_rotary_factor=0.5)}),
}


def describe(
    size: str = "8b",
    seq_len: int = 8192,
    vocab: int = 262272,
    layer_types: Optional[Sequence[str]] = None,
    experts_held: Optional[Tuple[int, int]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of a ZAYA1 of ``size``."""
    w = SIZES[size]
    kinds = tuple(layer_types or ("hybrid",) * w["num_hidden_layers"])
    if not set(kinds) <= set(w["rope_parameters"]):
        raise ValueError(f"ZAYA1's layers are {sorted(w['rope_parameters'])}; "
                         f"got {kinds}")
    if w["num_experts_per_tok"] != 1:
        raise ValueError("ZAYA1's router picks one choice a token")
    lo, hi = experts_held or (0, w["num_experts"])
    head_dim = w["head_dim"]

    def rope(p):
        rot = int(head_dim * p["partial_rotary_factor"])
        return RopeScheme(theta=float(p["rope_theta"]),
                          rotary_dim=0 if rot == head_dim else rot)

    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        n_kv_heads=w["num_key_value_heads"],
        head_size=head_dim,
        n_layers=len(kinds),
        d_ff=w["moe_intermediate_size"],
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=True,
        layers=tuple((kind, "moe") for kind in kinds),
        norm="rmsnorm",
        norm_eps=w["rms_norm_eps"],
        position="none",  # the attention kind brings its rotary scheme
        bias=False,
        attention_kinds=tuple(
            (name, AttentionKind(
                rope=rope(p),
                latent=LatentMix(taps=(w["cca_time0"], w["cca_time1"]))))
            for name, p in w["rope_parameters"].items()),
        moe=MoeConfig(
            experts_total=w["num_experts"], experts_held=(int(lo), int(hi)),
            k=1, d_ff=w["moe_intermediate_size"], router=ROUTERS[1],
            router_hidden=w["router_hidden_size"], skip_choice=True),
        residual_scale=True,
    )


@register_model("zaya")
def make_zaya(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The head is the
    fused chunked one wherever full logits would not fit
    (``models/lm.py fused_head_by_shape``)."""
    cfg = describe(**description)
    size = description.get("size", "8b")
    lo, hi = cfg.moe.experts_held
    return lm_bundle(cfg, f"zaya1-{size}-{cfg.n_layers}l-e{lo}-{hi}")

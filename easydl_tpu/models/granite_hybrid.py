"""Granite 4.0-H (IBM; HF model type ``granitemoehybrid``, the sizes without
experts): Mamba-2 mixers with a grouped-query attention layer every few,
every layer followed by a SwiGLU MLP, RMSNorm, no positions, a tied head and
four scalar multipliers. One description of ``models/transformer.py``'s
stack; nothing here but the published numbers.

``size="micro"`` is granite-4.0-h-micro as published
(huggingface.co/ibm-granite/granite-4.0-h-micro, ``config.json``): 40 layers,
2048 wide, 3.19B parameters. ``layer_types`` states another depth in the
published vocabulary (``"mamba"`` | ``"attention"``) — the benchmark's cell
runs the first six published entries, which is what fits one 16 GB chip with
AdamW's state.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import SsmConfig, TransformerConfig

_PUBLISHED_LAYERS = (("mamba",) * 5 + ("attention",)
                     + (("mamba",) * 9 + ("attention",)) * 3 + ("mamba",) * 4)

#: name -> widths; keys as the published ``config.json`` has them
SIZES: Dict[str, Dict[str, Any]] = {
    "micro": dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
        shared_intermediate_size=8192, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
        mamba_chunk_size=256, layer_types=_PUBLISHED_LAYERS),
    # tiny, for tests and dry runs: every mechanism, two Mamba layers to one
    # attention layer, two groups so the group broadcast is exercised
    "test": dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        shared_intermediate_size=128, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=16, layer_types=("mamba", "mamba", "attention")),
}
_MIXER = {"mamba": "mamba2", "attention": "attention"}


def describe(
    size: str = "micro",
    seq_len: int = 4096,
    vocab: int = 100352,
    layer_types: Optional[Sequence[str]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of a Granite 4.0-H of ``size``."""
    w = SIZES[size]
    kinds = tuple(layer_types or w["layer_types"])
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        n_kv_heads=w["num_key_value_heads"],
        n_layers=len(kinds),
        d_ff=w["shared_intermediate_size"],
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=True,
        layers=tuple((_MIXER[kind], "swiglu") for kind in kinds),
        norm="rmsnorm",
        norm_eps=1e-5,
        position="none",
        bias=False,
        embedding_multiplier=12.0,
        attention_multiplier=0.015625,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        ssm=SsmConfig(
            n_heads=w["mamba_n_heads"], head_dim=w["mamba_d_head"],
            d_state=w["mamba_d_state"], n_groups=w["mamba_n_groups"],
            d_conv=w["mamba_d_conv"], chunk=w["mamba_chunk_size"]),
    )


@register_model("granite_hybrid")
def make_granite_hybrid(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The head is the
    fused chunked one wherever full logits would not fit
    (``models/lm.py fused_head_by_shape``)."""
    cfg = describe(**description)
    size = description.get("size", "micro")
    return lm_bundle(cfg, f"granite-4.0-h-{size}-{cfg.n_layers}l")

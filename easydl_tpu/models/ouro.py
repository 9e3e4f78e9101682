"""Ouro (ByteDance Seed; HF model type ``ouro``), the looped language model
of "Scaling Latent Reasoning via Looped Language Models" (arXiv:2510.25741):
a dense decoder whose whole stack is applied ``total_ut_steps`` times over
the same parameters — multi-head attention with rotary positions, SwiGLU, an
RMSNorm before and after every sub-layer, an untied head and an exit gate
that read the normed state after every pass — trained on the expected loss
under the gate's exit distribution. One description of
``models/transformer.py``'s stack and ``models/lm.py``'s objective; nothing
here but the published numbers.

``size="2.6b"`` is Ouro-2.6B as published (huggingface.co/ByteDance/
Ouro-2.6B, ``config.json``): 48 layers, 2048 wide, 16 heads of 128, 2.67B
parameters. ``layer_types`` states another depth in the published vocabulary
(``"full_attention"``) — the benchmark's cell runs the first eight published
entries, which is what fits one 16 GB chip with AdamW's state.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import TransformerConfig

#: name -> widths; keys as the published ``config.json`` has them
SIZES: Dict[str, Dict[str, Any]] = {
    "2.6b": dict(
        hidden_size=2048, num_attention_heads=16, intermediate_size=5632,
        rope_theta=1000000.0, total_ut_steps=4,
        layer_types=("full_attention",) * 48),
    # tiny, for tests and dry runs: every mechanism
    "test": dict(
        hidden_size=64, num_attention_heads=4, intermediate_size=128,
        rope_theta=1000000.0, total_ut_steps=4,
        layer_types=("full_attention",) * 3),
}


def describe(
    size: str = "2.6b",
    seq_len: int = 4096,
    vocab: int = 49152,
    layer_types: Optional[Sequence[str]] = None,
    total_ut_steps: Optional[int] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of an Ouro of ``size``."""
    w = SIZES[size]
    kinds = tuple(layer_types or w["layer_types"])
    if set(kinds) != {"full_attention"}:
        raise ValueError(f"Ouro's layers are 'full_attention', got {kinds}")
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        n_layers=len(kinds),
        d_ff=w["intermediate_size"],
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=False,
        layers=(("attention", "swiglu"),) * len(kinds),
        norm="rmsnorm",
        norm_eps=1e-6,
        norm_placement="sandwich",
        position="rope",
        rope_theta=w["rope_theta"],
        bias=False,
        loops=total_ut_steps or w["total_ut_steps"],
        exit_gate=True,
    )


@register_model("ouro")
def make_ouro(exit_entropy_weight: float = 0.05, **description
              ) -> ModelBundle:
    """``description``: the arguments of :func:`describe`;
    ``exit_entropy_weight`` is the objective's ``beta``
    (``models/lm.py looplm_objective``). The head is the fused chunked one
    wherever the passes' full logits would not fit
    (``models/lm.py fused_head_by_shape``)."""
    cfg = describe(**description)
    size = description.get("size", "2.6b")
    return lm_bundle(cfg, f"ouro-{size}-{cfg.n_layers}l-x{cfg.loops}",
                     exit_entropy_weight=exit_entropy_weight)

"""GPT family — BASELINE config 4 ("GPT-2 345M data-parallel, Brain-driven
autoscale 8→32 chips"). The flagship model for the driver's entry point.

Sizes follow the GPT-2 paper naming; "345m" (a.k.a. GPT-2 medium:
24 layers, d_model 1024, 16 heads) is the benchmark config. Vocab is padded
to a multiple of 128 so the embedding/logits matmuls tile cleanly on the MXU.
"""

from __future__ import annotations

from typing import Dict, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import TransformerConfig

#: name -> (n_layers, d_model, n_heads)
SIZES: Dict[str, Tuple[int, int, int]] = {
    "124m": (12, 768, 12),
    "345m": (24, 1024, 16),
    "762m": (36, 1280, 20),
    "1558m": (48, 1600, 25),
    # tiny sizes for tests/dryruns
    "test": (2, 128, 4),
}


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
        pipeline_fn=pipeline_fn,
        pipeline_stages=pipeline_stages,
    )
    return lm_bundle(cfg, f"gpt-{size}")

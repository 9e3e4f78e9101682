"""What ZAYA1 tells the readers (``lib/told.py``): CCA, the flash kernels at
the latent's shape (``[2, 8192, 1024]``: 8 query heads of 128, the 2
key/value heads repeated to them in front of the kernels), looped."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_zaya
from lib.told import Kernel, Under, causal


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """ZAYA1's ACTIVE count (``lib/flops_zaya.py``: 6 a parameter of the
    matrix products, 12 a pair and head dimension the causal mask keeps),
    the routed experts' products at ZERO rows a token (the steady driver
    keeps no counter of its steps): at the seed's 8 / 17 rows a token and
    layer they are 213 of 1,143 MFLOP a token in the cell, so the share
    reads 19% of itself low, never high."""
    config = artifacts["config"]
    return flops_zaya.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Under(("attention",)),
            "head_loss_time_pct": Under(("lm_head_loss", "lm_head", "loss"))}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    return {"flash_fwd_roofline": Kernel("flash_fwd", causal("fwd")),
            "flash_bwd_roofline": Kernel("flash_bwd", causal("bwd"))}

"""What Kimi Linear tells the readers (``lib/told.py``): a delta rule with a
decay a channel three layers in four (``kda_fwd`` / ``kda_bwd`` under the
``kda`` scope), latent attention without positions the fourth — the flash
kernels at two head sizes, scores 192 deep, values 128 wide, 32 heads at
16,384: ``mla_fwd`` and the one-call ``mla_bwd``."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_joyai, flops_kimi_linear
from lib.told import Kernel, Under


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """Kimi Linear's ACTIVE count (``lib/flops_kimi_linear.py``: 6 a
    parameter of the matrix products, ``6 x (192 + 128)`` a pair and head the
    causal mask keeps in the latent layer, the recurrence's 294,912 a token
    and head in each KDA layer), the routed experts' products at ZERO rows a
    token: at the seed's 0.25 rows a token and sparse layer they are 42 of
    2,555 MFLOP a token in the cell, so the share reads under 2% of itself
    low, never high."""
    config = artifacts["config"]
    return flops_kimi_linear.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Under(("attention",)),
            "head_loss_time_pct": Under(("lm_head_loss", "lm_head", "loss"))}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    def mla(kind):
        # a call's batch and sequence are its first result's; the shared key
        # part read once a head as it lies in HBM
        return lambda call: flops_joyai.mla_flash_cost(
            kind, call["batch_heads"], call["seq"],
            config["num_attention_heads"],
            flops_kimi_linear.score_dim(config), config["v_head_dim"])
    return {"flash_fwd_roofline": Kernel("mla_fwd", mla("fwd")),
            "flash_bwd_roofline": Kernel("mla_bwd", mla("bwd"))}


def kda_cost(config: Dict[str, Any]) -> Dict[str, float]:
    return flops_kimi_linear.kda_cost(config)

"""What JoyAI-LLM-Flash tells the readers (``lib/told.py``): latent attention
(MLA), the flash kernels at two head sizes — scores 192 deep, values 128 wide,
32 heads at 8,192 — named ``mla_fwd`` and the one-call ``mla_bwd``."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_joyai
from lib.told import Kernel, Under


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """JoyAI-LLM's ACTIVE count (``lib/flops_joyai.py``: 6 a parameter of
    the matrix products, the head twice, ``6 x (192 + 128)`` a pair and head
    the causal mask keeps in each of the six attention layers), the routed
    experts' products at ZERO rows a token: at the seed's 0.5 rows a token
    and sparse layer they are 71 of 3,399 MFLOP a token in the cell, so the
    share reads under 2% of itself low, never high."""
    config = artifacts["config"]
    return flops_joyai.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Under(("attention",)),
            "head_loss_time_pct": Under(("lm_head_loss", "lm_head", "loss"))}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    def mla(kind):
        # a call's batch and sequence are its first result's; the shared
        # rotated key read once a head as it lies in HBM
        return lambda call: flops_joyai.mla_flash_cost(
            kind, call["batch_heads"], call["seq"],
            config["num_attention_heads"], flops_joyai.score_dim(config),
            config["v_head_dim"])
    return {"flash_fwd_roofline": Kernel("mla_fwd", mla("fwd")),
            "flash_bwd_roofline": Kernel("mla_bwd", mla("bwd"))}

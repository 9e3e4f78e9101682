"""What SDAR trained by block diffusion tells the readers (``lib/told.py``):
one attention kind, GQA 32 over 4 of 128, whose calls under the block mask
the program names ``bd_fwd`` and the one-call ``bd_bwd``."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_sdar
from lib.told import Kernel, Part


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """SDAR's ACTIVE count a DATA token (``lib/flops_sdar.py``: a layer's
    products twice — two rows a token — the head's once, ``6 x 2 x 128`` a
    pair and query head the block mask keeps), the routed experts' products
    at ZERO rows: at the cut's 1 row a row and layer they are 340 of 4,387
    MFLOP a token in the cell, so the share reads 8% of itself low, never
    high."""
    config = artifacts["config"]
    return flops_sdar.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_row=0.0)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Part("attention"),
            "head_loss_time_pct": Part("head_loss")}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    def masked(kind):
        # FLOPs of the mask's LIVE pairs alone; k and v at the key/value
        # heads, as they reach the kernels (read by index since PR 56)
        return lambda call: flops_sdar.flash_block_cost(
            kind, call["batch_heads"], call["seq"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["block_length"])
    return {"flash_fwd_roofline": Kernel("bd_fwd", masked("fwd")),
            "flash_bwd_roofline": Kernel("bd_bwd", masked("bwd"))}

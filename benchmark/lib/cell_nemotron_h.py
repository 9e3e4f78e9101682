"""What NemotronH tells the readers (``lib/told.py``): Mamba-2 mixers at 8 B/C
groups, ONE attention sub-layer at 32 query heads over 2 key/value heads of
128 (looped: ``flash_fwd``, the one-call ``flash_bwd``), relu² experts."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_nemotron
from lib.told import Kernel, Under, gqa


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """NemotronH's ACTIVE count (``lib/flops_nemotron.py``: 6 a parameter of
    the matrix products, ``6 x 2 x 128`` a pair and query head the causal
    mask keeps, three forwards of the scan in each M sub-layer), the routed
    experts' products at ZERO rows a token: at the seed's 0.375 rows a token
    and E sub-layer they are 90 of 2,153 MFLOP a token in the cell, so the
    share reads 4% of itself low, never high."""
    config = artifacts["config"]
    return flops_nemotron.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Under(("attention",)),
            "head_loss_time_pct": Under(("lm_head_loss", "lm_head", "loss"))}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    # k and v at the 2 key/value heads, as they reach the kernels (16 query
    # heads read each by index since PR 56)
    return {"flash_fwd_roofline": Kernel("flash_fwd", gqa("fwd", config)),
            "flash_bwd_roofline": Kernel("flash_bwd", gqa("bwd", config))}


def ssd_cost(config: Dict[str, Any]) -> Dict[str, float]:
    return dict(flops_nemotron.ssd_train_cost_per_token(config),
                layers=config["hybrid_override_pattern"].count("M"))

"""What the Granite 4.0-H hybrid tells the readers (``lib/told.py``): Mamba-2
mixers beside ONE attention layer in six, GQA 32 / 8 heads of 64 at 4,096 on
the flash kernels' looped side (64 block pairs a head: ``flash_fwd`` and the
one-call ``flash_bwd``)."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_ssd
from lib.told import Kernel, Part, causal


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """The hybrid's own count (``lib/flops_ssd.py``): 6 a parameter of the
    real tree, 12 x width x sequence for each attention layer, three
    forwards of the scan for each Mamba-2 layer."""
    config = artifacts["config"]
    return flops_ssd.hybrid_train_flops_per_token(
        artifacts["n_params"], config, config["kwargs"]["seq_len"])


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Part("attention"),
            "head_loss_time_pct": Part("head_loss")}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    return {"flash_fwd_roofline": Kernel("flash_fwd", causal("fwd")),
            "flash_bwd_roofline": Kernel("flash_bwd", causal("bwd"))}


def ssd_cost(config: Dict[str, Any]) -> Dict[str, float]:
    cost = flops_ssd.ssd_train_cost_per_token(**flops_ssd.ssd_shape(config))
    return dict(cost, layers=sum(
        1 for kind in config["layer_types"] if kind == "mamba"))

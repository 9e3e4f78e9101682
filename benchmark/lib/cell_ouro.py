"""What Ouro (a LoopLM: four passes over the same layers) tells the readers
(``lib/told.py``): heads of 128 at 4,096 on the flash kernels' looped side,
the four passes' heads one fused call beside the exit gate."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_looplm
from lib.told import Kernel, Part, Under, causal


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """The looped count (``lib/flops_looplm.py``): a pass is 6 a layer and
    head parameter plus 12 x width x sequence a layer, times the passes."""
    config = artifacts["config"]
    return flops_looplm.train_flops_per_token(
        config, config["kwargs"]["seq_len"])


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Part("attention"),
            # the heads over all passes' states in one fused call (the
            # passes share one path), the gate and the expected-exit loss
            "head_loss_time_pct": Under(("lm_head_loss", "lm_head", "loss",
                                         "exit_gate"))}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    return {"flash_fwd_roofline": Kernel("flash_fwd", causal("fwd")),
            "flash_bwd_roofline": Kernel("flash_bwd", causal("bwd"))}

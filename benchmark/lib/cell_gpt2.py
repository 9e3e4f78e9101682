"""What the GPT-2 configurations tell the readers (``lib/told.py``): learned
positions, heads of 64, the flash backward UNROLLED (at most 16 block pairs
a head at 1,024): two kernels, ``flash_bwd_dq`` and ``flash_bwd_dkv``."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops
from lib.told import Kernel, Part, causal


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """``lib/flops.train_flops_per_token`` on the real parameter tree's
    count: 6 a parameter and 12 x layers x width x sequence, the scores in
    full as the convention has it (a causal kernel needs half of them: by
    the needed FLOPs the share would read 6% lower at medium's sizes, 151
    of 2,431 MFLOP a token, and 5% at XL's)."""
    config = artifacts["config"]
    return flops.train_flops_per_token(
        artifacts["n_params"], config["n_layer"], config["n_embd"],
        config["kwargs"]["seq_len"])


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Part("attention"),
            "head_loss_time_pct": Part("head_loss")}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    return {"flash_fwd_roofline": Kernel("flash_fwd", causal("fwd")),
            "flash_dq_roofline": Kernel("flash_bwd_dq", causal("dq")),
            "flash_dkv_roofline": Kernel("flash_bwd_dkv", causal("dkv"))}

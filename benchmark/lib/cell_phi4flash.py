"""What Phi-4-mini-flash tells the readers (``lib/told.py``): Mamba-1 layers
(``sscan_fwd``, ``sscan_bwd``; x's convolution ``conv1d_*``), differential
attention under a window on the band path (``swa_fwd``, ``swa_bwd_dq``,
``swa_bwd_dkv``) and whole on the looped side (``diff_fwd``, the one-call
``diff_bwd``: the full layer and the cross layers), score heads of 64 against
values of 128, every layer a run of its own."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_phi4flash
from lib.told import Kernel, Part, Under


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """The step's own count (``lib/flops_phi4flash.py``): 6 a parameter of
    the matrix products, the pairs the causal mask or the band keeps at
    scores 64 deep and values 128 wide, three forwards of the scans' element
    operations."""
    config = artifacts["config"]
    return flops_phi4flash.train_flops_per_token(
        config, config["kwargs"]["seq_len"])


def _runs(config: Dict[str, Any], *wanted: str):
    """The program's names of the runs (one a layer: no two neighbours are
    of a kind) whose layer is one of the ``wanted`` kinds."""
    kinds = flops_phi4flash.kinds(config)
    return tuple("blocks" if len(kinds) == 1 else f"blocks_{i}"
                 for i, kind in enumerate(kinds) if kind in wanted)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Part("attention"),
            "head_loss_time_pct": Part("head_loss"),
            "band_attn_time_pct": Under(_runs(config, "window"),
                                        every=("attention",)),
            "full_attn_time_pct": Under(_runs(config, "full", "cross"),
                                        every=("attention",))}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    def cost(kind, window=0):
        return lambda call: flops_phi4flash.flash_diff_cost(
            config, kind, call["batch_heads"], call["seq"], window)
    band = config["sliding_window"]
    return {"flash_fwd_roofline": Kernel("diff_fwd", cost("fwd")),
            "flash_bwd_roofline": Kernel("diff_bwd", cost("bwd")),
            "band_flash_fwd_roofline": Kernel("swa_fwd", cost("fwd", band)),
            "band_flash_dq_roofline": Kernel("swa_bwd_dq", cost("dq", band)),
            "band_flash_dkv_roofline": Kernel("swa_bwd_dkv",
                                              cost("dkv", band))}


def selective_scan_cost(config: Dict[str, Any]) -> Dict[str, float]:
    return flops_phi4flash.selective_scan_cost(config)

"""What Laguna tells the readers (``lib/told.py``): two attention kinds, full
(48 query heads over 8 key/value heads of 128: ``flash_fwd``, the one-call
``flash_bwd``) and window-512 (64 heads: the band path's ``swa_fwd``,
``swa_bwd_dq``, ``swa_bwd_dkv``), each kind's layers in runs of their own."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_laguna
from lib.told import Kernel, Part, attention_kinds, causal


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """Laguna's ACTIVE count (``lib/flops_laguna.py``: 6 a parameter of the
    matrix products, 12 a pair and head dimension the causal mask or the
    band keeps), the routed experts' products at ZERO rows a token: the rows
    that land here drift over the window (0.0-1.7 a token) and the steady
    driver keeps no counter of its steps, so the share reads up to 5% low (a
    row a token and sparse layer is 75 MFLOP of 2.4 GFLOP), never high."""
    config = artifacts["config"]
    return flops_laguna.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return dict(attention_kinds(flops_laguna.runs(config)),
                head_loss_time_pct=Part("head_loss"))


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    def band(kind):
        # FLOPs of the band's pairs alone and the operands' bytes
        return lambda call: flops_laguna.flash_band_cost(
            kind, call["batch_heads"], call["seq"], call["head_dim"],
            config["head_dim"], config["sliding_window"])
    return {"flash_fwd_roofline": Kernel("flash_fwd", causal("fwd")),
            "flash_bwd_roofline": Kernel("flash_bwd", causal("bwd")),
            "band_flash_fwd_roofline": Kernel("swa_fwd", band("fwd")),
            "band_flash_dq_roofline": Kernel("swa_bwd_dq", band("dq")),
            "band_flash_dkv_roofline": Kernel("swa_bwd_dkv", band("dkv"))}

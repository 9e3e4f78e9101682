"""What Mellum 2 tells the readers (``lib/told.py``): two attention kinds of
one head count (32 over 4 of 128), full (``flash_fwd``, the one-call
``flash_bwd``) and window-1,024 on the band path beside a neighbour of 1,024
rows (``swa_fwd``, ``swa_bwd_dq``, ``swa_bwd_dkv``), each in runs of its own."""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_mellum
from lib.told import Kernel, Part, attention_kinds, gqa


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """Mellum 2's ACTIVE count (``lib/flops_mellum.py``: 6 a parameter of
    the matrix products, ``6 x 2 x 128`` a pair and query head the mask
    keeps — the band of 1,024 keys in the window layers), the routed
    experts' products at ZERO rows a token: at the cut's 2 rows a token and
    layer they are 297 of 1,493 MFLOP a token in the cell, so the share
    reads a fifth of itself low — the most of any cell — never high."""
    config = artifacts["config"]
    return flops_mellum.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return dict(attention_kinds(flops_mellum.runs(config)),
                head_loss_time_pct=Part("head_loss"))


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    def band(kind):
        # FLOPs of the band's pairs alone; bytes with the neighbour's re-read
        return lambda call: flops_mellum.flash_band_cost(
            kind, call["batch_heads"], call["seq"], call["head_dim"],
            config["head_dim"], config["sliding_window"])
    # the full layer's k and v at the 4 key/value heads, as they reach the
    # kernels (8 query heads read each by index since PR 56)
    return {"flash_fwd_roofline": Kernel("flash_fwd", gqa("fwd", config)),
            "flash_bwd_roofline": Kernel("flash_bwd", gqa("bwd", config)),
            "band_flash_fwd_roofline": Kernel("swa_fwd", band("fwd")),
            "band_flash_dq_roofline": Kernel("swa_bwd_dq", band("dq")),
            "band_flash_dkv_roofline": Kernel("swa_bwd_dkv", band("dkv"))}

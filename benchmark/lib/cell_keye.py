"""What Keye's language model tells the readers (``lib/told.py``): one
attention kind, GQA 32 over 4 of 128 behind a learned index, whose attention
calls the program names ``dsa_fwd`` and the one-call ``dsa_bwd``, and the
index's two kernels of its own, ``index_select`` and ``index_kl``
(``lib/index_roofline.py`` reads them)."""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

from lib import flops_keye
from lib.told import Kernel, Part


def train_flops_per_token(artifacts: Dict[str, Any]) -> float:
    """The ACTIVE count a token (``lib/flops_keye.py``: attention by the
    SELECTED pairs, the index's scores by the causal pairs), the routed
    experts' products at ZERO rows: at the cut's 1 row a row and layer they
    are 170 of 1,861 MFLOP a token in the cell, so the share reads 9% of
    itself low, never high."""
    config = artifacts["config"]
    return flops_keye.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_row=0.0)


def scopes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"attn_time_pct": Part("attention"),
            "head_loss_time_pct": Part("head_loss")}


def kernels(config: Dict[str, Any]) -> Dict[str, Kernel]:
    def selected(kind):
        # FLOPs of the SELECTED pairs alone, whatever tiles a kernel visits
        return lambda call: flops_keye.flash_selected_cost(
            kind, call["batch_heads"], call["seq"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["sa_config"]["topk"])
    return {"flash_fwd_roofline": Kernel("dsa_fwd", selected("fwd")),
            "flash_bwd_roofline": Kernel("dsa_bwd", selected("bwd"))}


def index_kernels(config: Dict[str, Any]
                  ) -> Dict[str, Tuple[str, Callable[[], Dict[str, float]]]]:
    """The index's own Mosaic kernels, which are no flash calls: quantity ->
    (the program's ``name=``, ``cost()``: what ONE sequence needs of it in
    ONE layer)."""
    def of(cost):
        return lambda: cost(config, config["kwargs"]["seq_len"])
    return {
        "index_scores_roofline": ("index_select",
                                  of(flops_keye.index_select_cost)),
        "index_bwd_roofline": ("index_kl", of(flops_keye.index_loss_cost))}

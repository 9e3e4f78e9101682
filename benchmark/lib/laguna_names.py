"""Device time of Laguna's parts by the program's own names, beside
``lib/looplm_names.py`` (any of several names): operations whose path holds
ALL of some names and ANY of others (the ``attention`` scope of the window
layers' runs), a kernel's calls told by its name, and the grouped products
under ``experts`` told by their primitive.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, List, Optional

from lib import flops, flops_laguna, peaks, scope_names, scope_reduce

SWA_KERNELS = {"swa_fwd": "fwd", "swa_bwd_dq": "dq", "swa_bwd_dkv": "dkv"}
ROUTE_SCOPES = ("router", "dispatch", "combine")


def _self_seconds(artifacts: Dict[str, Any]):
    """``(paths, self seconds by instruction, total seconds)`` of the
    traced run, or None where it has no trace with whole paths."""
    found = scope_reduce.of_run(artifacts)
    if not found or not found["whole_paths"]:
        return None
    path = scope_reduce.trace_file()
    return (found["paths"],
            scope_names._self_seconds(path, os.path.getmtime(path)),
            found["total_s"])


def run_names(config: Dict[str, Any], kind: str) -> List[str]:
    """The program's names (``blocks_<i>``) of the runs of layers whose
    attention is of ``kind``."""
    runs = flops_laguna.runs(config)
    return ["blocks" if len(runs) == 1 else f"blocks_{i}"
            for i, (k, _, _) in enumerate(runs) if k == kind]


def pct_under(artifacts: Dict[str, Any], every: Iterable[str],
              some: Iterable[str]) -> Optional[float]:
    """Share of the busy time of operations whose path holds every name of
    ``every`` and one of ``some``; None where there is no trace or no such
    operation (a program without the names)."""
    traced = _self_seconds(artifacts)
    if traced is None:
        return None
    paths, seconds, total = traced
    every, some = set(every), set(some)
    ops = []
    for op, path in paths.items():
        names = set(scope_reduce.names_on(path)[1])
        if every <= names and some & names:
            ops.append(op)
    if not ops:
        return None
    return 100.0 * sum(seconds.get(op, 0.0) for op in ops) / total


def grouped_products_seconds(artifacts: Dict[str, Any]) -> Optional[float]:
    """Self seconds of the grouped matrix products in the traced window.
    They are kernels of the COMPILER's own (``jax.lax.ragged_dot`` on a
    TPU), and it gives them no name stack: their ``op_name`` is the bare
    ``ragged-dot-...`` and no scope of the program's is on it, so they are
    told by that name — nothing else in the program is a ragged product.
    None where there is no trace or no such operation."""
    traced = _self_seconds(artifacts)
    if traced is None:
        return None
    paths, seconds, _ = traced
    ops = [op for op, path in paths.items()
           if path.startswith("ragged-dot") and "metadata" not in path]
    return sum(seconds.get(op, 0.0) for op in ops) if ops else None


def pct_with_grouped_products(artifacts: Dict[str, Any], name: str
                              ) -> Optional[float]:
    """Share of the busy time under the program's scope ``name`` plus the
    grouped products' (which belong under ``moe/experts`` and carry no
    name): None where the scope is not in the program that ran."""
    under = scope_names.name_pct(artifacts, name)
    products = grouped_products_seconds(artifacts)
    if under is None:
        return None
    total = scope_reduce.of_run(artifacts)["total_s"]
    return under + 100.0 * (products or 0.0) / total


def attention_pct(artifacts: Dict[str, Any], kind: str) -> Optional[float]:
    config = artifacts["config"]
    if "mlp_layer_types" not in config:
        return None
    return pct_under(artifacts, ["attention"], run_names(config, kind))


def swa_roofline(artifacts: Dict[str, Any], kernel: str) -> Optional[float]:
    """Least time the chip could take for the calls of one windowed kernel
    that ran — FLOPs of the band's pairs only and the operands' bytes
    (``lib/flops_laguna.flash_band_cost``) — over the time they took."""
    found = scope_reduce.of_run(artifacts)
    calls = artifacts.get("flash_calls")
    config = artifacts["config"]
    if not found or not calls or "sliding_window" not in config:
        return None
    kind = artifacts["device"]["kind"]
    peak_f = peaks.peak(kind, "bf16_flops_per_s")
    peak_b = peaks.peak(kind, "hbm_bytes_per_s")
    least = took = 0.0
    for call in calls:
        names = scope_reduce.names_on(found["paths"].get(call["name"], ""))[1]
        ran = artifacts["trace_summary"]["ops"].get(call["name"])
        if kernel not in names or not ran:
            continue
        # lib/hlo.py reads a call's first result [batch, seq, heads·d]
        cost = flops_laguna.flash_band_cost(
            SWA_KERNELS[kernel], call["batch_heads"], call["seq"],
            call["head_dim"], config["head_dim"], config["sliding_window"])
        least += ran["calls"] * flops.roofline_seconds(
            cost["flops"], cost["bytes"], peak_f, peak_b)["seconds"]
        took += ran["seconds"]
    return 100.0 * least / took if took else None

"""Device time of Mellum 2's parts by the program's own names, beside
``lib/looplm_names.py``, ``lib/laguna_names.py`` and ``lib/nemotron_names.py``:
any of several names at once, each operation counted once; the ``attention``
scope of one kind's runs of layers; and the shares of their rooflines of the
three band kernels at window 1,024 and of the full layer's flash forward at
32 query heads over 4 key/value heads, FLOPs and bytes by
``lib/flops_mellum.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional

from lib import (flops, flops_mellum, laguna_names, looplm_names, peaks,
                 scope_reduce)

#: the band kernels by the names the program gives them, and their kinds
SWA_KERNELS = {"swa_fwd": "fwd", "swa_bwd_dq": "dq", "swa_bwd_dkv": "dkv"}
ROUTER_SCOPES = ("router",)


def is_mellum(artifacts: Dict[str, Any]) -> bool:
    return artifacts.get("config", {}).get("model_type") == "mellum"


def pct_under_any(artifacts: Dict[str, Any], names: Iterable[str]
                  ) -> Optional[float]:
    """Share of the busy time of the operations whose path holds any of the
    program's ``names``. None where this is no Mellum 2 run, there is no
    trace with whole paths, or no operation's path holds a name."""
    if not is_mellum(artifacts):
        return None
    return looplm_names.pct_under_any(artifacts, names)


def run_names(config: Dict[str, Any], kind: str) -> List[str]:
    """The program's names (``blocks_<i>``) of the runs of layers whose
    attention is of ``kind``."""
    runs = flops_mellum.runs(config)
    return ["blocks" if len(runs) == 1 else f"blocks_{i}"
            for i, (k, _, _) in enumerate(runs) if k == kind]


def attention_pct(artifacts: Dict[str, Any], kind: str) -> Optional[float]:
    """Share of the busy time under the ``attention`` scope of the runs of
    layers of one attention ``kind``."""
    if not is_mellum(artifacts):
        return None
    return laguna_names.pct_under(
        artifacts, ["attention"], run_names(artifacts["config"], kind))


def _calls_roofline(artifacts: Dict[str, Any], kernel: str, cost
                    ) -> Optional[float]:
    """Least time the chip could take for the calls named ``kernel`` that
    ran — ``cost(call)`` gives a call's FLOPs and bytes — over the time they
    took. A call is told by the name the program gives it; its batch and
    sequence are its first result's (``lib/hlo.py`` reads ``[batch, seq,
    heads x head_dim]``)."""
    found = scope_reduce.of_run(artifacts) if is_mellum(artifacts) else None
    calls = artifacts.get("flash_calls")
    if not found or not calls:
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
        needs = cost(call)
        least += ran["calls"] * flops.roofline_seconds(
            needs["flops"], needs["bytes"], peak_f, peak_b)["seconds"]
        took += ran["seconds"]
    return 100.0 * least / took if took else None


def swa_roofline(artifacts: Dict[str, Any], kernel: str) -> Optional[float]:
    """One band kernel's share of its roofline: FLOPs of the band's pairs
    at ``sliding_window`` and the bytes its cells read
    (``lib/flops_mellum.flash_band_cost``)."""
    config = artifacts.get("config", {})
    return _calls_roofline(
        artifacts, kernel, lambda call: flops_mellum.flash_band_cost(
            SWA_KERNELS[kernel], call["batch_heads"], call["seq"],
            call["head_dim"], config["head_dim"], config["sliding_window"]))


def flash_fwd_roofline(artifacts: Dict[str, Any]) -> Optional[float]:
    """The full layer's ``flash_fwd`` calls' share of their roofline
    (``lib/flops_mellum.flash_fwd_cost``)."""
    config = artifacts.get("config", {})
    return _calls_roofline(
        artifacts, "flash_fwd", lambda call: flops_mellum.flash_fwd_cost(
            call["batch_heads"], call["seq"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]))

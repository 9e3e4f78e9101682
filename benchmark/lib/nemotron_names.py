"""Device time of NemotronH's parts by the program's own names, beside
``lib/looplm_names.py``, ``lib/laguna_names.py``, ``lib/zaya_names.py`` and
``lib/joyai_names.py``: any of several names at once, each operation counted
once; the Mamba-2 scan's share of its roofline at eight groups and chunks of
128; and the flash forward's at 32 query heads over 2 key/value heads, FLOPs
and bytes by ``lib/flops_nemotron.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

from lib import flops, flops_nemotron, looplm_names, peaks, scope_reduce

HEAD_SCOPES = ("lm_head_loss", "lm_head", "loss")
#: the expert layer outside its experts: the float32 router, the sort and the
#: gathers, the weighted sums back to the tokens
ROUTER_SCOPES = ("router", "dispatch", "combine")


def is_nemotron(artifacts: Dict[str, Any]) -> bool:
    return artifacts.get("config", {}).get("model_type") == "nemotron_h"


def pct_under_any(artifacts: Dict[str, Any], names: Iterable[str]
                  ) -> Optional[float]:
    """Share of the busy time of the operations whose path holds any of the
    program's ``names``. None where this is no NemotronH run, there is no
    trace with whole paths, or no operation's path holds a name (a program
    without them: the parent's has no ``gated_norm``)."""
    if not is_nemotron(artifacts):
        return None
    return looplm_names.pct_under_any(artifacts, names)


def ssd_roofline(artifacts: Dict[str, Any]) -> Optional[float]:
    """Least time the chip could take for the scans of the traced window —
    ``trace_steps`` steps of ``global_batch`` sequences through every ``M``
    sub-layer, forward and backward — over the seconds spent under ``ssd``
    (which hold the recomputed forward too: it is time, not work)."""
    if not is_nemotron(artifacts):
        return None
    seconds = looplm_names.seconds_under_any(artifacts, ("ssd",))
    if not seconds:
        return None
    config, traffic = artifacts["config"], artifacts["traffic"]
    cost = flops_nemotron.ssd_train_cost_per_token(config)
    tokens = (traffic["trace_steps"] * traffic["global_batch"]
              * config["kwargs"]["seq_len"]
              * config["hybrid_override_pattern"].count("M"))
    kind = artifacts["device"]["kind"]
    least = flops.roofline_seconds(
        tokens * cost["flops"], tokens * cost["bytes"],
        peaks.peak(kind, "bf16_flops_per_s"),
        peaks.peak(kind, "hbm_bytes_per_s"))["seconds"]
    return 100.0 * least / seconds


def flash_fwd_roofline(artifacts: Dict[str, Any]) -> Optional[float]:
    """Least time the chip could take for the ``flash_fwd`` calls that ran
    over the time they took. A call is told by the name the program gives it;
    its batch and sequence are its first result's (``lib/hlo.py`` reads
    ``[batch, seq, heads x head_dim]``), the heads the configuration's."""
    found = scope_reduce.of_run(artifacts) if is_nemotron(artifacts) else None
    calls = artifacts.get("flash_calls")
    if not found or not calls:
        return None
    config = artifacts["config"]
    kind = artifacts["device"]["kind"]
    peak_f = peaks.peak(kind, "bf16_flops_per_s")
    peak_b = peaks.peak(kind, "hbm_bytes_per_s")
    least = took = 0.0
    for call in calls:
        names = scope_reduce.names_on(found["paths"].get(call["name"], ""))[1]
        ran = artifacts["trace_summary"]["ops"].get(call["name"])
        if "flash_fwd" not in names or not ran:
            continue
        cost = flops_nemotron.flash_fwd_cost(
            call["batch_heads"], call["seq"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"])
        least += ran["calls"] * flops.roofline_seconds(
            cost["flops"], cost["bytes"], peak_f, peak_b)["seconds"]
        took += ran["seconds"]
    return 100.0 * least / took if took else None

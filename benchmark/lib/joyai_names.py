"""Device time of JoyAI-LLM's parts by the program's own names, beside
``lib/looplm_names.py``, ``lib/laguna_names.py`` and ``lib/zaya_names.py``:
any of several names at once, each operation counted once; and the
latent-attention flash kernels' share of their roofline (``mla_fwd``,
``mla_bwd_dq``, ``mla_bwd_dkv``: the kernels at two head sizes), FLOPs and
bytes by ``lib/flops_joyai.mla_flash_cost``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

from lib import flops, flops_joyai, looplm_names, peaks, scope_reduce

#: the maps around the kernels: into the latents, their norms, up to the
#: heads, the way back
PROJECTION_SCOPES = ("mla_down", "mla_norm", "mla_up", "mla_out")
#: the rotation, and the rotated key vector copied beside every head's part
KEY_ROPE_SCOPES = ("rope", "mla_key")
#: kernel name -> the kind ``lib/flops_joyai.mla_flash_cost`` knows it by
MLA_KERNELS = {"mla_fwd": "fwd", "mla_bwd_dq": "dq", "mla_bwd_dkv": "dkv"}
HEAD_SCOPES = ("lm_head_loss", "lm_head", "loss")


def is_joyai(artifacts: Dict[str, Any]) -> bool:
    return artifacts.get("config", {}).get("model_type") == "joyai_llm_flash"


def pct_under_any(artifacts: Dict[str, Any], names: Iterable[str]
                  ) -> Optional[float]:
    """Share of the busy time of the operations whose path holds any of the
    program's ``names``. None where this is no JoyAI-LLM run, there is no
    trace with whole paths, or no operation's path holds a name (a program
    without them)."""
    if not is_joyai(artifacts):
        return None
    return looplm_names.pct_under_any(artifacts, names)


def flash_roofline(artifacts: Dict[str, Any], kernel: str) -> Optional[float]:
    """Least time the chip could take for the calls of one latent-attention
    kernel that ran over the time they took. A call is told by the name the
    program gives it; its batch and sequence are its first result's
    (``lib/hlo.py`` reads ``[batch, seq, heads x size]``), heads and the two
    head sizes the configuration's."""
    found = scope_reduce.of_run(artifacts) if is_joyai(artifacts) else None
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
        if kernel not in names or not ran:
            continue
        cost = flops_joyai.mla_flash_cost(
            MLA_KERNELS[kernel], call["batch_heads"], call["seq"],
            config["num_attention_heads"], flops_joyai.score_dim(config),
            config["v_head_dim"])
        least += ran["calls"] * flops.roofline_seconds(
            cost["flops"], cost["bytes"], peak_f, peak_b)["seconds"]
        took += ran["seconds"]
    return 100.0 * least / took if took else None

"""Device time under a name of the program's that ``lib/scope_reduce.py``'s
fixed table of parts does not list (``ssm``, ``ssd``, ``conv1d``): the same
trace, the same paths and the same self times, filtered by name here.

A name's share is over the busy time of the traced window, every pass
(forward, backward, recomputed). Where the program that ran has no
operation under the name — a program from before the scope existed — there
is nothing to report, and the readers return None rather than 0.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional

from lib import flops, flops_ssd, peaks, scope_reduce, trace_reduce


def seconds_under(name: str, paths: Dict[str, str],
                  self_seconds: Dict[str, float]) -> Optional[float]:
    """Self seconds of the operations whose path holds ``name`` as one of
    the program's names; None where no operation's does."""
    ops = [op for op, path in paths.items()
           if name in scope_reduce.names_on(path)[1]]
    if not ops:
        return None
    return sum(self_seconds.get(op, 0.0) for op in ops)


@functools.lru_cache(maxsize=1)
def _self_seconds(path: str, mtime: float) -> Dict[str, float]:
    return trace_reduce.time_by_op(trace_reduce.load_xplane(path))


def seconds_of_run(artifacts: Dict[str, Any], name: str) -> Optional[float]:
    """``seconds_under`` for the traced run the artifacts are of."""
    found = scope_reduce.of_run(artifacts)
    if not found or not found["whole_paths"]:
        return None
    path = scope_reduce.trace_file()
    return seconds_under(name, found["paths"],
                         _self_seconds(path, os.path.getmtime(path)))


def name_pct(artifacts: Dict[str, Any], name: str) -> Optional[float]:
    seconds = seconds_of_run(artifacts, name)
    if seconds is None:
        return None
    return 100.0 * seconds / scope_reduce.of_run(artifacts)["total_s"]


def ssd_roofline_pct(config: Dict[str, Any], traffic: Dict[str, Any],
                     seconds: float, peak_flops: float, peak_bytes: float
                     ) -> float:
    """Least time the chip could take for the scans of the traced window —
    ``trace_steps`` steps of ``global_batch`` sequences through every Mamba
    layer, forward and backward, FLOPs and bytes from ``lib/flops_ssd.py``
    — over the ``seconds`` spent under ``ssd`` (which hold the recomputed
    forward too: it is time, not work)."""
    cost = flops_ssd.ssd_train_cost_per_token(**flops_ssd.ssd_shape(config))
    n_mamba = sum(1 for kind in config["layer_types"] if kind == "mamba")
    tokens = (traffic["trace_steps"] * traffic["global_batch"]
              * config["kwargs"]["seq_len"] * n_mamba)
    least = flops.roofline_seconds(tokens * cost["flops"],
                                   tokens * cost["bytes"], peak_flops,
                                   peak_bytes)["seconds"]
    return 100.0 * least / seconds


def ssd_roofline_of_run(artifacts: Dict[str, Any]) -> Optional[float]:
    seconds = seconds_of_run(artifacts, "ssd")
    if not seconds:
        return None
    kind = artifacts["device"]["kind"]
    return ssd_roofline_pct(
        artifacts["config"], artifacts["traffic"], seconds,
        peaks.peak(kind, "bf16_flops_per_s"),
        peaks.peak(kind, "hbm_bytes_per_s"))

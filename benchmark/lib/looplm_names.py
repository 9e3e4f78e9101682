"""Device time of a looped model's parts by the program's own names, beside
``lib/scope_names.py`` (one name) and ``lib/scope_reduce.py`` (a fixed table
of parts): several names at once, each operation counted once.

A scan over the passes around the scan over the layers puts a second
``while/body`` (and flax's ``Transformer.one_pass``) on every path:
``scope_reduce.names_on`` drops the wrappers wherever they stand, so the
existing readers hold. The passes share one path, and a share read here is
of all four.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Optional

from lib import scope_names, scope_reduce

FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
SANDWICH_NORMS = ("ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out")
HEAD_AND_GATE = ("lm_head_loss", "lm_head", "loss", "exit_gate")


def seconds_under_any(artifacts: Dict[str, Any], names: Iterable[str]
                      ) -> Optional[float]:
    """Self seconds, in the traced window, of the operations whose path
    holds any of ``names``; None where the run has no trace or no
    operation's path holds one (a program from before the names)."""
    found = scope_reduce.of_run(artifacts)
    if not found or not found["whole_paths"]:
        return None
    wanted = set(names)
    ops = [op for op, path in found["paths"].items()
           if wanted & set(scope_reduce.names_on(path)[1])]
    if not ops:
        return None
    path = scope_reduce.trace_file()
    seconds = scope_names._self_seconds(path, os.path.getmtime(path))
    return sum(seconds.get(op, 0.0) for op in ops)


def pct_under_any(artifacts: Dict[str, Any], names: Iterable[str]
                  ) -> Optional[float]:
    seconds = seconds_under_any(artifacts, names)
    if seconds is None:
        return None
    return 100.0 * seconds / scope_reduce.of_run(artifacts)["total_s"]

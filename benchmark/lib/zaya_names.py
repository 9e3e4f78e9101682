"""Device time of ZAYA1's parts by the program's own names, beside
``lib/looplm_names.py`` (any of several names) and ``lib/laguna_names.py``:
the grouped matrix products told by their primitive wherever the compiler
puts its name — bare (``ragged-dot...``, no name stack: ``laguna_names``
reads those) or, since the expert layer's pieces (PR 32), as the LAST
component of a whole path (``.../moe/experts/ragged_dot_general``) — each
operation counted once.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Optional

from lib import scope_names, scope_reduce

#: what CCA adds outside matrix products and kernels
MIX_SCOPES = ("cca_conv", "value_shift", "qk_norm")
HEAD_SCOPES = ("lm_head_loss", "lm_head", "loss")


def is_zaya(artifacts: Dict[str, Any]) -> bool:
    return artifacts.get("config", {}).get("model_type") == "zaya"


def _is_grouped_product(path: str) -> bool:
    last = path.rsplit("/", 1)[-1]
    return (last.startswith(("ragged-dot", "ragged_dot"))
            and "metadata" not in path)


def pct_under_any(artifacts: Dict[str, Any], names: Iterable[str],
                  grouped_products: bool = False) -> Optional[float]:
    """Share of the busy time of the operations whose path holds any of the
    program's ``names`` — with ``grouped_products`` also of the grouped
    matrix products, which belong under ``moe/experts`` and may carry no
    name. None where this is no ZAYA1 run, there is no trace with whole
    paths, or no operation's path holds a name (a program without them)."""
    if not is_zaya(artifacts):
        return None
    found = scope_reduce.of_run(artifacts)
    if not found or not found["whole_paths"]:
        return None
    wanted = set(names)
    named = {op for op, path in found["paths"].items()
             if wanted & set(scope_reduce.names_on(path)[1])}
    if not named:
        return None
    if grouped_products:
        named |= {op for op, path in found["paths"].items()
                  if _is_grouped_product(path)}
    path = scope_reduce.trace_file()
    seconds = scope_names._self_seconds(path, os.path.getmtime(path))
    return 100.0 * sum(seconds.get(op, 0.0) for op in named) \
        / found["total_s"]


def flash_roofline(artifacts: Dict[str, Any], kernel: str) -> Optional[float]:
    """Roofline share of one flash kernel at the latent's shape (``[2,
    8192, 1024]``: 8 query heads of 128, the 2 key/value heads repeated to
    them in front of the kernel), FLOPs and bytes from the call's shape as
    ``flash_fwd_roofline`` counts them (``lib/scope_reduce.py``)."""
    if not is_zaya(artifacts):
        return None
    return scope_reduce.kernel_roofline_of_run(artifacts, kernel)

"""The roofline share of a learned index's own kernels, which
``lib/hlo.flash_calls`` does not list (they are no flash calls: other
results, other names). A cell's module states them (``index_kernels(config)
-> {quantity: (the program's kernel name, cost() -> {"flops", "bytes"} of ONE
sequence in ONE layer)}``); the time is what the operations under that name took in
the traced window (``lib/scope_names.py``: the kernel's ``name=`` is a
component of its instruction's path), the work that of ``trace_steps`` steps
of ``global_batch`` sequences through every layer ONCE — a kernel that a
rematerialised forward ran a second time reads half: it is time, not work."""

from __future__ import annotations

from typing import Any, Dict, Optional

from lib import flops, peaks, scope_names, scope_reduce, told


def pct(artifacts: Dict[str, Any], quantity: str) -> Optional[float]:
    module = told.module_of(artifacts)
    stated = getattr(module, "index_kernels", None)
    if stated is None or not scope_reduce.of_run(artifacts):
        return None
    name, cost = stated(artifacts["config"])[quantity]
    seconds = scope_names.seconds_under(artifacts, (), (name,))
    if not seconds:
        return None
    cost = cost()
    config, traffic = artifacts["config"], artifacts["traffic"]
    calls = (traffic["trace_steps"] * traffic["global_batch"]
             * len(config["layer_types"]))
    kind = artifacts["device"]["kind"]
    least = flops.roofline_seconds(
        calls * cost["flops"], calls * cost["bytes"],
        peaks.peak(kind, "bf16_flops_per_s"),
        peaks.peak(kind, "hbm_bytes_per_s"))["seconds"]
    return 100.0 * least / seconds

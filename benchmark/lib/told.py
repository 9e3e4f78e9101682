"""What a configuration TELLS the per-layer readers, and the readers' shared
arithmetic on it.

A reader under ``benchmark/layer_metrics/`` is named for a quantity
(``mfu``, ``flash_bwd_roofline``, ``attn_time_pct``) and lists, in
``BENCHMARK.json``, every cell that has it. What differs from cell to cell —
the step's FLOP count, the kernels' names and what a call of each costs,
which of the program's scopes hold a part — is stated by ONE module a
configuration, found from data as the steady driver finds its check: the
configuration file's ``readers.module`` names ``benchmark/lib/<module>.py``.
No reader and nothing here knows a configuration's name; a later PR brings a
quantity to its cell by adding its module and appending its cell to the
``workloads`` lists. A module states, each where the cell has it:

``train_flops_per_token(artifacts) -> float``
    model FLOPs of one training token, forward and backward, recomputation
    not counted (``mfu``).
``scopes(config) -> {quantity: Under | Part}``
    where a share of the device's time stands in the program's own names —
    only the quantities whose names differ by configuration (``attn_``,
    ``head_loss_``, ``full_attn_``, ``band_attn_time_pct``); a scope that is
    the program's own in every model (``moe``, ``experts``, ``rope``, ``ssm``)
    is in its reader.
``kernels(config) -> {quantity: Kernel}``
    the flash kernel a ``*_roofline`` reads, by the name the program gives
    it, and the FLOPs and HBM bytes one of its calls needs.
``ssd_cost(config) -> {"flops", "bytes", "layers"}``
    the Mamba-2 scan's cost a token and layer, and how many layers scan.

A reader whose cell's module does not state its quantity has nothing to
read and returns None; so does every reader of a run without a trace.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

from lib import flops, peaks, scope_names, scope_reduce


class Under(NamedTuple):
    """Operations whose path holds one of ``some`` and every one of
    ``every`` of the program's names (``lib/scope_names.pct_under``)."""
    some: Tuple[str, ...]
    every: Tuple[str, ...] = ()


class Part(NamedTuple):
    """A part of ``lib/scope_reduce.py``'s table: each operation in the part
    of the first listed scope on its path."""
    part: str


class Kernel(NamedTuple):
    """A Mosaic kernel by the program's ``name=`` and ``cost(call)``, the
    ``{"flops", "bytes"}`` one call needs (``call``: an entry of
    ``lib/hlo.flash_calls``)."""
    name: str
    cost: Callable[[Dict[str, Any]], Dict[str, float]]


def causal(kind: str) -> Callable[[Dict[str, Any]], Dict[str, float]]:
    """The cost of a causal flash call of ``kind`` on its first result's
    ``[batch, seq, heads x head_dim]``, k and v counted at the QUERY's heads
    (``lib/flops.flash_causal_cost``; the width taken as one head's: the same
    FLOPs and matrix bytes). That is what a call of equal head counts reads;
    a grouped-query call is handed k and v at the key/value heads since PR
    56 and reads fewer bytes than this counts (its FLOPs set its roofline)."""
    return lambda call: flops.flash_causal_cost(
        kind, call["batch_heads"], call["seq"], call["head_dim"])


def gqa(kind: str, config: Dict[str, Any]
        ) -> Callable[[Dict[str, Any]], Dict[str, float]]:
    """The cost of a causal flash call of ``kind`` (``fwd``, ``bwd``) under
    grouped-query attention by the configuration's head counts: k and v (dk,
    dv) at the key/value heads, as they reach the kernels since PR 56 (a
    query head reads its shared head by index; nothing is repeated in HBM)
    (``lib/flops.flash_gqa_cost``)."""
    return lambda call: flops.flash_gqa_cost(
        kind, call["batch_heads"], call["seq"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"])


def run_names(runs, kind: str) -> Tuple[str, ...]:
    """The program's names (``blocks_<i>``; ``blocks`` where there is one)
    of the runs of equal layers whose attention is of ``kind``; ``runs``:
    ``[(attention kind, ...)]`` in the stack's order."""
    return tuple("blocks" if len(runs) == 1 else f"blocks_{i}"
                 for i, run in enumerate(runs) if run[0] == kind)


def attention_kinds(runs) -> Dict[str, Under]:
    """Where a stack of two attention kinds, each kind's layers in runs of
    their own, has ``full_attn_`` and ``band_attn_time_pct``: the
    ``attention`` scope of each kind's runs."""
    return {f"{name}_attn_time_pct": Under(run_names(runs, kind),
                                           every=("attention",))
            for name, kind in (("full", "full_attention"),
                               ("band", "sliding_attention"))}


def module_of(artifacts: Dict[str, Any]):
    """The module the run's configuration names, or None."""
    name = artifacts.get("config", {}).get("readers", {}).get("module")
    return importlib.import_module(f"lib.{name}") if name else None


def _stated(artifacts: Dict[str, Any], table: str, quantity: str):
    module = module_of(artifacts)
    state = getattr(module, table, None)
    return state(artifacts["config"]).get(quantity) if state else None


def share_pct(artifacts: Dict[str, Any], quantity: str) -> Optional[float]:
    """Share of the device's busy time where the cell's module says
    ``quantity`` stands, every pass of differentiation."""
    if not scope_reduce.of_run(artifacts):
        return None
    where = _stated(artifacts, "scopes", quantity)
    if where is None:
        return None
    if isinstance(where, Part):
        return scope_reduce.part_pct(artifacts, where.part)
    return scope_names.pct_under(artifacts, where.every, where.some)


def kernel_roofline_pct(artifacts: Dict[str, Any], quantity: str
                        ) -> Optional[float]:
    """Least time the chip could take for the calls of the kernel the
    cell's module names for ``quantity`` — a call told by the program's name
    on its instruction's path, FLOPs and bytes by the module's ``cost`` —
    over the time they took in the traced window."""
    found = scope_reduce.of_run(artifacts)
    calls = artifacts.get("flash_calls")
    if not found or not calls:
        return None
    kernel = _stated(artifacts, "kernels", quantity)
    if kernel is None:
        return None
    kind = artifacts["device"]["kind"]
    peak_f = peaks.peak(kind, "bf16_flops_per_s")
    peak_b = peaks.peak(kind, "hbm_bytes_per_s")
    least = took = 0.0
    for call in calls:
        names = scope_reduce.names_on(found["paths"].get(call["name"], ""))[1]
        ran = artifacts["trace_summary"]["ops"].get(call["name"])
        if kernel.name not in names or not ran:
            continue
        cost = kernel.cost(call)
        least += ran["calls"] * flops.roofline_seconds(
            cost["flops"], cost["bytes"], peak_f, peak_b)["seconds"]
        took += ran["seconds"]
    return 100.0 * least / took if took else None


def mfu_pct(artifacts: Dict[str, Any]) -> Optional[float]:
    """Tokens per second of the window times the module's FLOPs a token,
    over chips times the published bf16 peak. Off the chip there is no peak
    to hold a rate against (and a TPU of a kind the table lacks is an
    error, raised by ``lib/peaks.py``)."""
    module = module_of(artifacts)
    if "step_s" not in artifacts or artifacts["device"]["platform"] != "tpu" \
            or not hasattr(module, "train_flops_per_token"):
        return None
    per_token = module.train_flops_per_token(artifacts)
    rate = artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]
    peak = peaks.peak(artifacts["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * rate * per_token / (artifacts["chips"] * peak)


def ssd_roofline_pct(artifacts: Dict[str, Any]) -> Optional[float]:
    """Least time the chip could take for the scans of the traced window —
    ``trace_steps`` steps of ``global_batch`` sequences through every
    scanning layer, forward and backward, by the module's ``ssd_cost`` —
    over the seconds spent under ``ssd`` (which hold the recomputed forward
    too: it is time, not work)."""
    module = module_of(artifacts)
    seconds = scope_names.seconds_under(artifacts, (), ("ssd",))
    if not seconds or not hasattr(module, "ssd_cost"):
        return None
    config, traffic = artifacts["config"], artifacts["traffic"]
    cost = module.ssd_cost(config)
    tokens = (traffic["trace_steps"] * traffic["global_batch"]
              * config["kwargs"]["seq_len"] * cost["layers"])
    kind = artifacts["device"]["kind"]
    least = flops.roofline_seconds(
        tokens * cost["flops"], tokens * cost["bytes"],
        peaks.peak(kind, "bf16_flops_per_s"),
        peaks.peak(kind, "hbm_bytes_per_s"))["seconds"]
    return 100.0 * least / seconds

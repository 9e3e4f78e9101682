"""From a profiler trace to device time by pass and by part of the program —
read from the names the program gives its own operations.

Every operation of a compiled jax program carries its name stack as
``op_name`` metadata: ``jit(train_step)/transpose(jvp(Transformer))/while/
body/closed_call/checkpoint/rematted_computation/blocks/attention/q/
dot_general``. jax marks the pass (``jvp(...)`` forward, ``transpose(jvp(...))``
backward, ``rematted_computation`` recomputed), flax names the modules, and
the program's ``jax.named_scope``s and its Pallas kernels' ``name=`` the rest.
The ``.xplane.pb`` a ``jax.profiler`` session writes holds, beside the device
events, the HLO of every module that ran (plane ``/host:metadata``, one event
metadata a module, stat ``Hlo Proto``); an event of a device's "XLA Ops" line
is named by its instruction, and that instruction's ``metadata.op_name`` in
the embedded HLO is its path. So the executable that ran says what each of
its operations belongs to, and nothing is compiled again to find out.

``load`` reads one trace file into a plain structure:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...],         # as trace_reduce has it
     "paths": {"fusion.796": "jit(train_step)/...", ...}}

and everything else here is a pure function of that structure, tested on a
recorded fixture. ``jax.profiler.ProfileData`` shows neither a plane's event
metadata nor bytes stats, so ``op_paths`` reads the file's protobuf wire
format itself (field numbers from tsl's ``xplane.proto`` and xla's
``hlo.proto``, checked against the generated modules when this was written).
The interval arithmetic is ``trace_reduce``'s.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

from lib import trace_reduce

PASSES = ("fwd", "bwd", "remat", "none")
PARTS = ("attention", "ssm", "ffn", "moe", "head_loss", "optimizer",
         "accumulate", "other", "unscoped")

#: scope or module name on a path -> part. The listed scopes do not nest in
#: one another (a block's mixer is ``attention`` or ``ssm``, its FFN ``ffn``
#: or ``moe``; a multi-token-prediction module's layer stands with the
#: blocks), so the first found decides.
_PART_OF = {
    "attention": "attention", "ssm": "ssm", "ffn": "ffn", "moe": "moe",
    "tok_emb.attend": "head_loss", "lm_head": "head_loss",
    "loss": "head_loss", "lm_head_loss": "head_loss",
    "optimizer": "optimizer", "grad_norm": "optimizer",
    "accumulate": "accumulate",
}
#: path components that name a transformation or a control-flow construct,
#: not a place in the program
_WRAPPERS = frozenset((
    "while", "body", "cond", "closed_call", "checkpoint",
    "rematted_computation", "remat", "scan", "pjit", "core_call",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_lin", "shard_map", "branch", "cond_branch", "named"))
_CALL = re.compile(r"^(\w+)\((.*)\)$")


# ------------------------------------------------------------------ names
def names_on(path: str) -> Tuple[List[str], List[str]]:
    """``(transformations, names)`` of a path: ``transpose(jvp(loss))`` gives
    the transformations ``transpose`` and ``jvp`` and the name ``loss``. The
    last component is the primitive and is dropped; every ``jit(...)``
    component is a wrapper whatever it wraps (``jit(train_step)`` heads every
    path, ``jit(_where)`` is a jax.numpy helper)."""
    transforms: List[str] = []
    names: List[str] = []
    for part in path.split("/")[:-1]:
        while True:
            m = _CALL.match(part)
            if not m:
                break
            transforms.append(m.group(1))
            part = "" if m.group(1) == "jit" else m.group(2)
        if part and part not in _WRAPPERS:
            names.append(part)
    return transforms, names


def classify(path: str) -> Dict[str, Optional[str]]:
    """``{"pass", "part"}`` of one operation's path. Pass and part
    are two partitions: every path has exactly one of each. An operation
    outside the differentiated function (optimizer, accumulation, gradient
    norm) has pass ``none``; one whose path holds a name of the program that
    is no listed scope (``cast_params``, ``tok_emb``, ``ln_f``) is part
    ``other``; one that holds none at all is ``unscoped``."""
    transforms, names = names_on(path)
    if "rematted_computation" in path.split("/"):
        pass_ = "remat"
    elif "transpose" in transforms:
        pass_ = "bwd"
    elif "jvp" in transforms:
        pass_ = "fwd"
    else:
        pass_ = "none"
    part = next((_PART_OF[n] for n in names if n in _PART_OF),
                "other" if names else "unscoped")
    return {"pass": pass_, "part": part}


# ----------------------------------------------------------------- shares
def seconds_by_class(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Self seconds of the traced window's device operations (averaged over
    the devices) by ``(pass, part)``, and the largest unscoped operations by
    name. None where no operation ran."""
    window = trace_reduce.window_of(trace)
    if window is None or not trace["devices"]:
        return None
    paths = trace.get("paths", {})
    n = len(trace["devices"])
    cells: Dict[Tuple[str, str], float] = {}
    unscoped: Dict[str, float] = {}
    class_of: Dict[str, Tuple[str, str]] = {}  # a step repeats its names
    for events in trace["devices"].values():
        for name, ns in trace_reduce.self_times(
                trace_reduce.clip(events, *window)):
            key = class_of.get(name)
            if key is None:
                found = classify(paths.get(name, ""))
                key = class_of[name] = (found["pass"], found["part"])
            cells[key] = cells.get(key, 0.0) + ns / 1e9 / n
            if key[1] == "unscoped":
                unscoped[name] = unscoped.get(name, 0.0) + ns / 1e9 / n
    total = sum(cells.values())
    if not total:
        return None
    return {"total_s": total, "cells": cells,
            "unscoped_ops": sorted(unscoped.items(), key=lambda kv: -kv[1])}


def shares(trace: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """What the readers take: ``pass_pct`` and ``part_pct`` (each sums to
    100), the whole ``table_pct`` (``"pass/part"`` keys, zero cells left
    out), ``total_s`` (equal to the busy time where operations nest
    properly) and the ``top`` largest unscoped operations with their share.
    None where no operation ran."""
    found = seconds_by_class(trace)
    if found is None:
        return None
    total = found["total_s"]
    pass_pct = {p: 0.0 for p in PASSES}
    part_pct = {p: 0.0 for p in PARTS}
    for (pass_, part), seconds in found["cells"].items():
        pass_pct[pass_] += 100.0 * seconds / total
        part_pct[part] += 100.0 * seconds / total
    return {
        "total_s": total, "pass_pct": pass_pct, "part_pct": part_pct,
        "table_pct": {f"{a}/{b}": 100.0 * s / total
                      for (a, b), s in sorted(found["cells"].items()) if s},
        "unscoped_ops": [[name, 100.0 * s / total]
                         for name, s in found["unscoped_ops"][:top]]}


def named_parts(trace: Dict[str, Any]) -> bool:
    """Whether the program that ran names its parts at all: the trace of a
    program without the scopes has passes (jax's own) but no part except
    ``other`` and ``unscoped``, and a reader of a part then has nothing to
    report, which is not the same as a share of zero."""
    return any(classify(path)["part"] not in ("other", "unscoped")
               for path in trace.get("paths", {}).values())


def whole_paths(trace: Dict[str, Any]) -> bool:
    """Whether the executable that ran carries name stacks at all: more than
    half of its named instructions have a path, not a bare primitive
    (``"dot_general"``). The chip's had none before PR 23 — all but a few
    helper calls — and shares read from such a program say nothing."""
    named = [p for p in trace.get("paths", {}).values() if p]
    return 2 * sum("/" in p for p in named) > len(named)


# ------------------------------------------------------------- the file
def fields(buf: memoryview) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            if byte < 0x80:
                return value
            shift += 7

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
        elif kind == 2:
            size = varint()
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            yield key >> 3, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {kind}")


def _only(buf: memoryview, number: int) -> Iterator[Any]:
    return (value for field, value in fields(buf) if field == number)


def op_paths(path: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` over the HLO modules the trace file
    embeds. XSpace.planes=1; XPlane.name=2, .event_metadata=4 (a map: entry
    value=2); XEventMetadata.stats=5; XStat.bytes_value=6; HloProto
    .hlo_module=1; HloModuleProto.computations=3; HloComputationProto
    .instructions=2; HloInstructionProto.name=1, .metadata=7; OpMetadata
    .op_name=2. Of two modules with an instruction of one name (the step and
    some small helper), the larger module's says."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    modules: List[Dict[str, str]] = []
    for plane in _only(space, 1):
        if not any(bytes(v) == b"/host:metadata" for v in _only(plane, 2)):
            continue
        for entry in _only(plane, 4):
            for metadata in _only(entry, 2):
                for stat in _only(metadata, 5):
                    for proto in _only(stat, 6):
                        modules.append(_module_paths(proto))
    out: Dict[str, str] = {}
    for module in sorted(modules, key=len):
        out.update(module)
    return out


def _module_paths(hlo_proto: memoryview) -> Dict[str, str]:
    out = {}
    for module in _only(hlo_proto, 1):
        for computation in _only(module, 3):
            for instruction in _only(computation, 2):
                name = op_name = ""
                for field, value in fields(instruction):
                    if field == 1:
                        name = bytes(value).decode()
                    elif field == 7:
                        op_name = "".join(
                            bytes(v).decode() for v in _only(value, 2))
                out[name] = op_name
    return out


def load(path: str) -> Dict[str, Any]:
    return dict(trace_reduce.load_xplane(path), paths=op_paths(path))


def trace_file() -> Optional[str]:
    """The newest trace under the benchmark's run directories: a run empties
    its own directory before it starts, so after a traced run that is the
    run's own (``benchmark/.work/<cell>/trace``; the artifacts do not name
    the cell)."""
    work = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".work")
    files = glob.glob(os.path.join(work, "*", "trace", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime: float) -> Optional[Dict[str, Any]]:
    trace = load(path)
    found = shares(trace)
    if found is None:
        return None
    whole = whole_paths(trace)
    return dict(found, paths=trace["paths"], whole_paths=whole,
                named_parts=whole and named_parts(trace))


def of_run(artifacts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``shares`` of the traced run the artifacts are of, with the ``paths``
    and ``named_parts``; reduced once for all readers of a process. None
    where the run took no device trace."""
    if not artifacts.get("trace_summary"):
        return None
    path = trace_file()
    if path is None:
        return None
    return _reduced(path, os.path.getmtime(path))


def pass_pct(artifacts: Dict[str, Any], pass_: str) -> Optional[float]:
    """A pass's share, or None where the program that ran carries no name
    stacks (op_names cut to the bare primitive, as the chip had them before
    PR 23)."""
    found = of_run(artifacts)
    if not found or not found["whole_paths"]:
        return None
    return found["pass_pct"][pass_]


def part_pct(artifacts: Dict[str, Any], part: str) -> Optional[float]:
    """A part's share, or None where the program that ran names no part (a
    program from before the scopes)."""
    found = of_run(artifacts)
    if not found or not found["named_parts"]:
        return None
    return found["part_pct"][part]

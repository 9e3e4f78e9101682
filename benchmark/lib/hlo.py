"""What a compiled step program says about itself: its Mosaic (Pallas)
calls by name, and the bytes it needs on each device."""

from __future__ import annotations

import re
from typing import Any, Dict, List

#: ``%name = <result type> custom-call(...), custom_call_target="tpu_custom_call"``
_MOSAIC = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s*custom-call\(.*"
    r'custom_call_target="tpu_custom_call"')
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?:[\w.]+\()*([^()]*)\)*$")
#: the end of a flash kernel's name says what it computes, and how many
#: arrays that gives: the forward ``out`` and the ``lse`` column, the
#: one-call backward dq, dk and dv, the two-call backward dq | dk and dv
_KIND = re.compile(r"_(fwd|bwd|bwd_dq|bwd_dkv)$")
_RESULTS = {"fwd": 2, "bwd": 3, "bwd_dq": 1, "bwd_dkv": 2}
KIND = {"fwd": "fwd", "bwd": "bwd", "bwd_dq": "dq", "bwd_dkv": "dkv"}


def kernel_name(instruction: str, line: str) -> str:
    """The ``name=`` the program gave a Mosaic call: the component in front
    of ``pallas_call`` on the instruction's ``op_name`` path, and where the
    line carries no metadata the instruction's own name, which the compiler
    makes from it (``%flash_fwd.3``, ``%transpose_jvp_flash_bwd_dq__.1``)."""
    found = _OP_NAME.search(line)
    parts = found.group(1).split("/") if found else []
    if len(parts) >= 2 and parts[-1] == "pallas_call":
        # a kernel differentiated on its own stands inside the transforms'
        # names: ``transpose(jvp(flash_bwd_dq))``
        return _WRAPPED.match(parts[-2]).group(1)
    return instruction.split(".")[0].rstrip("_")


def flash_calls(hlo_text: str) -> List[Dict[str, Any]]:
    """The Mosaic calls of ``ops/flash_attention.py`` in a compiled program,
    told by the NAME the program gives each (``flash_fwd``, ``flash_bwd``,
    ``flash_bwd_dq``, ``flash_bwd_dkv``; ``swa_*`` on the band path, ``mla_*``
    at two head sizes): the name's end is the kind — ``fwd``, ``bwd`` (the
    looped backward, one call of three results), ``dq``, ``dkv`` — and the
    call is listed where its results are as many as that kind gives, on
    ``[batch, seq, heads x head_dim]`` (so ``rope_fwd`` and ``rope_bwd``, one
    array each, ``ssd_*`` and ``conv1d_*``, rank 4, and the grouped products
    are not). Returns ``[{"name", "kernel", "kind", "batch_heads", "seq",
    "head_dim"}]``: ``name`` is the HLO instruction's, which is what the
    device trace calls the event, ``kernel`` the program's, and the three
    sizes are the first result's as they stand (batch, sequence, width)."""
    calls = []
    for line in hlo_text.splitlines():
        m = _MOSAIC.match(line)
        if not m:
            continue
        kernel = kernel_name(m.group(1), line)
        kind = _KIND.search(kernel)
        arrays = [(dt, [int(x) for x in dims.split(",") if x])
                  for dt, dims in _ARRAY.findall(m.group(2))]
        if not kind or len(arrays) != _RESULTS[kind.group(1)] \
                or len(arrays[0][1]) != 3:
            continue
        if kind.group(1) == "fwd" and (arrays[1][0] != "f32"
                                       or arrays[1][1][-1] != 1):
            continue
        bh, seq, hd = arrays[0][1]
        calls.append({"name": m.group(1), "kernel": kernel,
                      "kind": KIND[kind.group(1)], "batch_heads": bh,
                      "seq": seq, "head_dim": hd})
    return calls


def step_memory(compiled: Any) -> Dict[str, int]:
    """``compiled.memory_analysis()`` as plain numbers, per device."""
    mem = compiled.memory_analysis()
    return {"argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)}

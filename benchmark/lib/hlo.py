"""What a compiled step program says about itself: its Mosaic (Pallas)
calls by kind, and the bytes it needs on each device."""

from __future__ import annotations

import re
from typing import Any, Dict, List

#: ``%name = <result type> custom-call(...), custom_call_target="tpu_custom_call"``
_MOSAIC = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s*custom-call\(.*"
    r'custom_call_target="tpu_custom_call"')
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def flash_calls(hlo_text: str) -> List[Dict[str, Any]]:
    """The Mosaic calls of ``ops/flash_attention.py`` in a compiled program,
    told apart by their results (the three ``pl.pallas_call``s carry no
    name): forward gives ``(bf16 [BH, S, d], f32 [BH, S, 1])``, dq one array
    of the operands' type, dkv two. Returns ``[{"name", "kind",
    "batch_heads", "seq", "head_dim"}]`` with the HLO instruction's name,
    which is what the device trace calls the event."""
    calls = []
    for line in hlo_text.splitlines():
        m = _MOSAIC.match(line)
        if not m:
            continue
        arrays = [(dt, [int(x) for x in dims.split(",") if x])
                  for dt, dims in _ARRAY.findall(m.group(2))]
        if not arrays or len(arrays[0][1]) != 3:
            continue
        if len(arrays) == 1:
            kind = "dq"
        elif len(arrays) == 2 and arrays[1][0] == "f32" \
                and arrays[1][1][-1] == 1:
            kind = "fwd"
        elif len(arrays) == 2:
            kind = "dkv"
        else:
            continue
        bh, seq, hd = arrays[0][1]
        calls.append({"name": m.group(1), "kind": kind, "batch_heads": bh,
                      "seq": seq, "head_dim": hd})
    return calls


def step_memory(compiled: Any) -> Dict[str, int]:
    """``compiled.memory_analysis()`` as plain numbers, per device."""
    mem = compiled.memory_analysis()
    return {"argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)}

"""ops: share of the device's busy time under ``rope`` in a stack with two
rotary schemes — whole heads at theta 1e4 in the window layers, YaRN over the
first half of a head in the full ones: the rotary kernel's calls on q and k,
every pass of differentiation (lib/scope_names.py). Time and no roofline: the
kernel's operands stay in VMEM (PERF.md section 7)."""

from lib import scope_names


def read(artifacts):
    if "sliding_window" not in artifacts["config"]:
        return None
    return scope_names.name_pct(artifacts, "rope")

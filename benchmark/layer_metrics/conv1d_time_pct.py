"""ops: share of the device's busy time under ``conv1d``, the causal
depthwise convolutions of width 4 in front of the scan and their SiLU, in
every pass (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.name_pct(artifacts, "conv1d")

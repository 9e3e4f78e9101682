"""ops: share of the device's busy time under ``conv1d``, the causal depthwise
convolutions in front of the scan (x, B and C) and their SiLU, in every pass
(lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('conv1d',))

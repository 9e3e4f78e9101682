"""model: share of the device's busy time under a Mamba-2 layer's ``ssm`` scope
— its norm, the five input projections, the convolutions, the scan, the gated
norm, the output projection and the residual add, the sibling of ``attention``
— in every pass (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('ssm',))

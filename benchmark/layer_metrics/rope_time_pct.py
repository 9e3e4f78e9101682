"""ops: share of the device's busy time under ``rope``, the rotation of q and
k by position between the projections and the kernels, every pass of the
loop and of differentiation (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.name_pct(artifacts, "rope")

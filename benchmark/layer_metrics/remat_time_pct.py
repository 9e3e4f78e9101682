"""model: share of the device's busy time spent recomputing the forward pass
inside the backward one — a path through ``rematted_computation``, the
flash forward kernel's second run included (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.pass_pct(artifacts, "remat")

"""model: share of the device's busy time under the block's ``attention``
scope in a looped stack — both sandwich norms, the q, k, v and out
projections, rotary, the residual add and the three flash kernels — all
passes of the loop and of differentiation (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.part_pct(artifacts, "attention")

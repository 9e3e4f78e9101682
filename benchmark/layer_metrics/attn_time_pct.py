"""model: share of the device's busy time under the block's ``attention``
scope — ``ln_attn``, the q, k, v and out projections, the residual add and
the three flash kernels — in every pass (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.part_pct(artifacts, "attention")

"""ops: roofline share of the flash dq kernel alone at head_dim 128 (one
head a 128-lane block, the looped side at 4,096), told by the name the
program gives it (``flash_bwd_dq``), FLOPs and bytes from the call's shape as
``flash_dq_roofline`` counts them (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.kernel_roofline_of_run(artifacts, "flash_bwd_dq")

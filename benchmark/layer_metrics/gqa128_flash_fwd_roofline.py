"""ops: roofline share of the flash fwd kernel alone in the full attention
layers (48 query heads reading 8 key/value heads of 128, repeated to the
query's count in front of the kernel; the looped side at 8,192), told by the
name the program gives it (``flash_fwd``), FLOPs and bytes from the call's shape
as ``flash_fwd_roofline`` counts them (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.kernel_roofline_of_run(artifacts, "flash_fwd")

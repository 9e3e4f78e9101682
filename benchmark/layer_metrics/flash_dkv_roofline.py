"""ops: roofline share of the flash dkv kernel alone, told by the name the
program gives it (``flash_bwd_dkv`` on the instruction's path), its shape from the
same instruction, FLOPs and bytes as ``flash_roofline`` counts them for that
kind (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.kernel_roofline_of_run(artifacts, "flash_bwd_dkv")

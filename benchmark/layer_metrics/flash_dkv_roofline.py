"""ops: roofline share of the UNROLLED flash backward's dk/dv kernel alone
(``flash_bwd_dkv``: four products a pair), told by the name the program gives
it, its shape from the same instruction, FLOPs and bytes by the cell's
module's cost of a call (lib/told.py, lib/flops.py)."""

from lib import told


def read(artifacts):
    return told.kernel_roofline_pct(artifacts, "flash_dkv_roofline")

"""ops: roofline share of the band path's dq kernel alone (``swa_bwd_dq``: the flash
kernels under a sliding window): FLOPs of the band's pairs only (``0 <= i - j <
sliding_window``) and the bytes its cells read, by the cell's module's cost of
a call (lib/told.py), over the time its calls took in the traced window: a
kernel that multiplies keys outside the band reads low, not high."""

from lib import told


def read(artifacts):
    return told.kernel_roofline_pct(artifacts, "band_flash_dq_roofline")

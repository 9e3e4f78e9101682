"""ops: roofline share of the full-attention flash FORWARD kernel alone — the
calls the program names as the cell's module says (``flash_fwd``; ``mla_fwd``
at two head sizes), FLOPs of the causal pairs and the operands' bytes by that
module's cost of a call (lib/told.py), against the chip's published peaks, over
the time the calls took in the traced window. A cell's band-path kernels are
``band_flash_fwd_roofline``'s."""

from lib import told


def read(artifacts):
    return told.kernel_roofline_pct(artifacts, "flash_fwd_roofline")

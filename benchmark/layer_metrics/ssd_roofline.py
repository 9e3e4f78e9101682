"""ops: roofline share of the Mamba-2 scan: the FLOPs and HBM bytes its matrix
products need, forward and backward, from shapes (the cell's module's
``ssd_cost``: lib/told.py, lib/flops_ssd.py), against the chip's published
peaks, over the time spent under ``ssd`` in the traced window, recomputation
included in the time and not in the work."""

from lib import told


def read(artifacts):
    return told.ssd_roofline_pct(artifacts)

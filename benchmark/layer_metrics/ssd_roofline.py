"""ops: roofline share of the Mamba-2 scan: the FLOPs and HBM bytes its
matrix products need, forward and backward, from shapes (lib/flops_ssd.py),
against the chip's published peaks, over the time spent under ``ssd`` in the
traced window, recomputation included in the time and not in the work
(lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.ssd_roofline_of_run(artifacts)

"""ops: roofline share of the Mamba-2 scan at 8 B/C groups and chunks of 128: the
FLOPs and HBM bytes its four matrix products need, forward and backward, from
shapes (lib/flops_nemotron.py, lib/flops_ssd.py), against the chip's published
peaks, over the time spent under ``ssd`` in the traced window, recomputation
included in the time and not in the work (lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.ssd_roofline(artifacts)

"""ops: share of the device's busy time under ``conv1d``, the causal depthwise
convolutions of 4 taps over x (4,096 channels), B and C (8 x 128 each) and
their SiLU, in every pass (lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, ("conv1d",))

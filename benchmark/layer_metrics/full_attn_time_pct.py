"""model: share of the device's busy time under the ``attention`` scope of the
FULL-attention layers' runs in a stack that also has window layers (the runs'
names by the cell's module: lib/told.py), every pass."""

from lib import told


def read(artifacts):
    return told.share_pct(artifacts, "full_attn_time_pct")

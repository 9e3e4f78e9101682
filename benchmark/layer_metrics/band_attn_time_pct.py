"""model: share of the device's busy time under the ``attention`` scope of the
WINDOW layers' runs (the band path: norm, projections, rotary, k and v
repeated to the query heads, the three band kernels, a gate where the kind
has one, the residual add; the runs' names by the cell's module: lib/told.py),
every pass."""

from lib import told


def read(artifacts):
    return told.share_pct(artifacts, "band_attn_time_pct")

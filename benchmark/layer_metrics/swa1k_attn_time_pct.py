"""model: share of the device's busy time under the ``attention`` scope of the
WINDOW-1,024 layers' run (``blocks_0``) — norm, the four projections, rotary,
k and v repeated eightfold, the three band kernels, the residual add — every
pass (lib/mellum_names.py)."""

from lib import mellum_names


def read(artifacts):
    return mellum_names.attention_pct(artifacts, "sliding_attention")

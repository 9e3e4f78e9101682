"""model: share of the device's busy time in the four expert sub-layers: the
block's ``moe`` scope, from its norm to the add — the float32 router with its
selection bias over 128 experts, dispatch, the two-matrix grouped products over
the 8 experts held, combine, the shared expert of 3,712 — every pass
(lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, ("moe",))

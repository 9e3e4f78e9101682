"""model: share of the device's busy time in the five sparse layers' FFNs (the
module's among them): the block's ``moe`` scope, from its norm to the add — the
float32 router with its selection bias, dispatch, the grouped products over
the 16 experts held, combine, the shared expert — every pass of
differentiation (lib/joyai_names.py)."""

from lib import joyai_names


def read(artifacts):
    return joyai_names.pct_under_any(artifacts, ("moe",))

"""model: share of the device's busy time under the ``attention`` scope of the
one attention sub-layer at 8k — q at 32 heads, k and v at 2 key/value heads
repeated to them sixteenfold in front of the kernels, ``flash_fwd`` and the one
``flash_bwd``, the way back, the add — every pass (lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, ("attention",))

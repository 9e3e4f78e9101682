"""model: share of the device's busy time under the ``attention`` scope of the
FULL layer's run (``blocks_1``) — norm, the projections, the YaRN rotary, k
and v at 4 key/value heads repeated to 32, ``flash_fwd`` and the one
``flash_bwd``, the residual add — every pass (lib/mellum_names.py)."""

from lib import mellum_names


def read(artifacts):
    return mellum_names.attention_pct(artifacts, "full_attention")

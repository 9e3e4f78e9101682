"""model: share of the device's busy time under the ``mtp`` scope: the
multi-token-prediction module's join (``mtp_merge``: two norms and the 4096 ->
2048 map), its one sparse layer (its own ``attention`` and ``moe`` scopes under
it) and its final norm; its head's part is aside, in the one fused call both
heads share (``joyai_head_time_pct``); every pass (lib/joyai_names.py)."""

from lib import joyai_names


def read(artifacts):
    return joyai_names.pct_under_any(artifacts, ("mtp",))

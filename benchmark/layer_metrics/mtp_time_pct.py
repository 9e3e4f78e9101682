"""model: share of the device's busy time under the ``mtp`` scope: the
multi-token-prediction module's join (``mtp_merge``: two norms and the map),
its one sparse layer (its own ``attention`` and ``moe`` scopes under it) and
its final norm; its head's part is aside, in the one fused call both heads
share (``head_loss_time_pct``); every pass (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('mtp',))

"""ops: share of the device's busy time under ``router``: the float32 logits
at highest precision over all 64 experts, the softmax, top-8, the
renormalised weights, the entropy and the chosen mass — every pass
(lib/mellum_names.py)."""

from lib import mellum_names


def read(artifacts):
    return mellum_names.pct_under_any(artifacts, mellum_names.ROUTER_SCOPES)

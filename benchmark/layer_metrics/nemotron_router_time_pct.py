"""ops: share of the device's busy time of the expert sub-layers outside their
experts: ``router`` (the float32 logits at highest precision, sigmoid, the
selection bias, top-6 of 128), ``dispatch`` (the sort, a piece's gather and the
transpose's sum back to the tokens) and ``combine`` (the weighted sum back and
its transpose), in every pass (lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, nemotron_names.ROUTER_SCOPES)

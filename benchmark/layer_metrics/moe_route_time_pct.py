"""ops: share of the device's busy time routing — ``router`` (logits, scores,
top-k), ``dispatch`` (sort, offsets, the gather into the sorted buffer) and
``combine`` (weights, the gather back, the sum over a token's experts) —
every pass of differentiation (lib/looplm_names.py)."""

from lib import laguna_names, looplm_names


def read(artifacts):
    return looplm_names.pct_under_any(artifacts, laguna_names.ROUTE_SCOPES)

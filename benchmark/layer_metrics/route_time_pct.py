"""ops: share of the device's busy time of the expert layers outside their
experts: ``router`` (the float32 logits, scores, top-k), ``dispatch`` (the
sort, a piece's gather, the transpose's sum back to the tokens) and ``combine``
(the weighted sum back to the tokens and its transpose), every pass
(lib/scope_names.py). ``router_time_pct`` is the first of the three alone."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('router', 'dispatch', 'combine'))

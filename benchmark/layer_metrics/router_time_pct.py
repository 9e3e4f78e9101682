"""ops: share of the device's busy time under ``router`` ALONE: the float32
logits at highest precision (through a router MLP and its state where the
form has one), the scores, top-k, the weights and the router's counters —
every pass (lib/scope_names.py); ``route_time_pct`` holds ``dispatch`` and
``combine`` too."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('router',))

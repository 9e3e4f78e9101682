"""model: share of the device's busy time under ``gmu``, a gated memory unit's
two products and its gate on an earlier layer's scan output, in every pass
(lib/scope_names.py); nothing where the program has no such scope."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('gmu',))

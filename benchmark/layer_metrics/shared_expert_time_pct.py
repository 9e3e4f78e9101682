"""ops: share of the device's busy time under ``shared_expert``: the plain
products every token passes through beside the routed experts, every pass
(lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('shared_expert',))

"""model: share of the device's busy time spent in operations of the backward
pass — a path under ``transpose(jvp(...))``, the recomputed forward left out
(lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.pass_pct(artifacts, "bwd")

"""model: share of the device's busy time in the traced window spent in
operations of the forward pass — a path under ``jvp(...)`` that is neither
transposed nor recomputed (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.pass_pct(artifacts, "fwd")

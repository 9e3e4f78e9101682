"""ops: share of the device's busy time under ``qk_rmsnorm``, the RMSNorm over
each head's dimensions on q and on k between the projections and the rotary
kernel, every pass (lib/scope_names.py); nothing where the program has no such
scope."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('qk_rmsnorm',))

"""ops: share of the device's busy time under ``selective_scan``, a Mamba-1
layer's scan and the views in front of and behind its kernels, in every pass
(lib/scope_names.py); nothing where the program has no such scope."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('selective_scan',))

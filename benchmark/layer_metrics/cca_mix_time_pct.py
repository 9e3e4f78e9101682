"""ops: share of the device's busy time in what CCA adds outside matrix
products and kernels — ``cca_conv`` (both convolutions over the sequence and
the mean of the un-convolved q and k), ``value_shift``, ``qk_norm`` (the L2
norm and the temperature) — every pass (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('cca_conv', 'value_shift', 'qk_norm'))

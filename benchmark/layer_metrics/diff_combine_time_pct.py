"""ops: share of the device's busy time under ``diff_combine``, differential
attention outside its kernels — the pair's difference under lambda, the
RMSNorm over a pair's value and its scale — in every pass
(lib/scope_names.py); nothing where the program has no such scope."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('diff_combine',))

"""ops: share of the device's busy time under a delta-rule layer's ``kda``
scope — the chunked recurrence's kernels (``kda_fwd``, ``kda_bwd``) or their
``jax.numpy`` form, the per-chunk cumulative decays and the ``beta``-weighted
operands in front of them, and nothing else of the mixer — in every pass
(lib/scope_names.py). A program without the scope gives nothing to read."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('kda',))

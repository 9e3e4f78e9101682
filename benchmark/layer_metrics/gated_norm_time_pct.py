"""ops: share of the device's busy time under ``gated_norm``, the gated RMSNorm
behind the scan (``ops/ssd.py gated_rmsnorm``; over each B/C group's channels
apart where the configuration groups it), in every pass
(lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('gated_norm',))

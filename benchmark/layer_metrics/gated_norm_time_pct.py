"""ops: share of the device's busy time under ``gated_norm``, the gated RMSNorm
behind the scan taken over each B/C group's 512 channels apart
(``ops/ssd.py gated_rmsnorm``), in every pass; the scope is new with the
configuration: a program without it gives nothing to read
(lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, ("gated_norm",))

"""ops: share of the device's busy time under ``ssd``, the Mamba-2 chunked scan
alone (``ops/ssd.py ssd_scan``), in every pass (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('ssd',))

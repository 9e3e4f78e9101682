"""ops: share of the device's busy time under ``ssd``, the Mamba-2 chunked scan
alone (``ops/ssd.py ssd_scan``) at 8 B/C groups and 64 chunks of 128 a
sequence, in every pass (lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, ("ssd",))

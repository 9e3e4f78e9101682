"""ops: share of the device's busy time under ``shared_expert``: the two plain
products ``[16384, 2688] x [2688, 3712]`` and back with ``relu(.)^2`` between
them, which every token passes through, in every pass
(lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, ("shared_expert",))

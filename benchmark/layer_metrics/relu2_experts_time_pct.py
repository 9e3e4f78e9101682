"""ops: share of the device's busy time in the routed ungated experts of width
1,856: the ``experts`` scope — the named grouped kernels ``grouped_rows``,
``grouped_rows_t``, ``grouped_weights`` at ``[R, 2688] x [8, 2688, 1856]`` and
back, and ``relu(.)^2`` and its derivative between them — every pass
(lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, ("experts",))

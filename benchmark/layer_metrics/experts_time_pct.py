"""ops: share of the device's busy time in the routed experts: the ``experts``
scope — the named grouped kernels ``grouped_rows``, ``grouped_rows_t``,
``grouped_weights`` over the experts held and the activation between them —
every pass (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('experts',))

"""ops: share of the device's busy time in the routed SwiGLU experts of width
896: the ``experts`` scope — the named grouped kernels ``grouped_rows``,
``grouped_rows_t``, ``grouped_weights`` at ``[R, 2304] x [16, 2304, 896]``
(seven lane tiles of columns) and back, and ``silu(gate) * up`` and its
derivative between them — every pass (lib/mellum_names.py)."""

from lib import mellum_names


def read(artifacts):
    return mellum_names.pct_under_any(artifacts, ("experts",))

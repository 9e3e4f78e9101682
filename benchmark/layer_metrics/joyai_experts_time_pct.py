"""ops: share of the device's busy time in the routed experts of width 768:
the ``experts`` scope — the named grouped kernels ``grouped_rows``,
``grouped_rows_t``, ``grouped_weights`` at ``[R, 2048] x [16, 2048, 768]`` and
the activation between them — every pass (lib/joyai_names.py)."""

from lib import joyai_names


def read(artifacts):
    return joyai_names.pct_under_any(artifacts, ("experts",))

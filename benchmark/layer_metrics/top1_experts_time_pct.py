"""ops: share of the device's busy time in the routed experts of width 2048:
the ``experts`` scope (the activation between the grouped products, their
casts) AND the three grouped products over the experts held themselves, the
compiler's own kernels, told by their ``ragged-dot`` name as a path's last
component or bare (lib/zaya_names.py); every pass of differentiation."""

from lib import zaya_names


def read(artifacts):
    return zaya_names.pct_under_any(artifacts, ("experts",),
                                    grouped_products=True)

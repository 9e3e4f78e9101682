"""model: share of the device's busy time in the top-1 expert layers: the
block's ``moe`` scope, from its norm to the scaled add — the MLP router and
its state, dispatch, experts, combine — plus the grouped products wherever the
compiler put their name (lib/zaya_names.py); every pass of differentiation."""

from lib import zaya_names


def read(artifacts):
    return zaya_names.pct_under_any(artifacts, ("moe",),
                                    grouped_products=True)

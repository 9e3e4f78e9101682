"""ops: share of the device's busy time in the routed experts: the ``experts``
scope (the activation between the grouped products, their casts) plus the
three grouped products over the experts held themselves, the compiler's own
kernels, told by their ``ragged-dot`` name (lib/laguna_names.py); every pass
of differentiation."""

from lib import laguna_names


def read(artifacts):
    return laguna_names.pct_with_grouped_products(artifacts, "experts")

"""model: share of the device's busy time in the expert layers: the block's
``moe`` scope, from its norm to the residual add — router, dispatch, the
shared expert, combine — plus the grouped products, which are the compiler's
own kernels and carry no name of the program's (lib/laguna_names.py); every
pass of differentiation. ``ffn_time_pct``'s sibling: ``ffn`` is the dense
FFN's."""

from lib import laguna_names


def read(artifacts):
    return laguna_names.pct_with_grouped_products(artifacts, "moe")

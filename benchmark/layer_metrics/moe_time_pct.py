"""model: share of the device's busy time in the expert layers: the block's
``moe`` scope, from its norm to the residual add — router, dispatch, the
grouped products over the experts held, combine, a shared expert where there
is one — every pass (lib/scope_names.py). ``ffn_time_pct``'s sibling: ``ffn``
is the dense FFN's."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('moe',))

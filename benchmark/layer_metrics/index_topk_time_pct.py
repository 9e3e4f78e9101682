"""ops: share of the device's busy time under ``index_topk``, where a learned
index ranks each query's causal keys and packs the selection — on the chip ONE
kernel with the scores it ranks (``index_select``: no ``[L, L]`` array leaves
it), so the scores' products are in this share; every pass
(lib/scope_names.py). Kept across rematerialisation, it runs once a layer and
step."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('index_topk',))

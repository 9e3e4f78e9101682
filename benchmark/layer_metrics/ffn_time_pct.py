"""model: share of the device's busy time under the block's ``ffn`` scope —
``ln_mlp``, up, GELU, down and the residual add — in every pass
(lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.part_pct(artifacts, "ffn")

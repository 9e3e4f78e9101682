"""model: share of the device's busy time under the ``attention`` scope of the
WINDOW layers' runs — norm, projections, rotary, the windowed kernels, the
gate, the residual add — every pass of differentiation
(lib/laguna_names.py)."""

from lib import laguna_names


def read(artifacts):
    return laguna_names.attention_pct(artifacts, "sliding_attention")

"""model: share of the device's busy time under the four norms of a sandwich
layer (``ln_attn``, ``ln_attn_out``, ``ln_mlp``, ``ln_mlp_out``: each
sub-layer normed before and after), every pass; what XLA fuses into a
neighbouring product is read with that product (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('ln_attn', 'ln_attn_out', 'ln_mlp', 'ln_mlp_out'))

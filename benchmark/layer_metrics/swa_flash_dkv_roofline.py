"""ops: roofline share of the windowed flash dkv kernel alone (``swa_bwd_dkv``):
FLOPs of the band's pairs only (``0 <= i - j < sliding_window``) and the
operands' bytes (lib/flops_laguna.py), over the time its calls took in the
trace: a kernel that visits the whole triangle reads low, not high
(lib/laguna_names.py)."""

from lib import laguna_names


def read(artifacts):
    return laguna_names.swa_roofline(artifacts, "swa_bwd_dkv")

"""ops: roofline share of the kernel that forms a learned index's own loss AND
its three gradients in one pass (the program's ``index_kl``), by the SELECTED
pairs — the attention's scores again for its probabilities, the index's
scores, the two products of the gradient to its queries and key — and its
operands' bytes (lib/index_roofline.py, lib/flops_keye.py), against the chip's
published peaks, over the time the kernel took in the traced window. A kernel
that visits every causal tile reads low by the share of pairs it need not
have made."""

from lib import index_roofline


def read(artifacts):
    return index_roofline.pct(artifacts, "index_bwd_roofline")

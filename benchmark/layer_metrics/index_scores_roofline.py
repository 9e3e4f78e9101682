"""ops: roofline share of a learned index's score-and-select kernel (the
program's ``index_select``): ``2 x heads x dim`` FLOPs a CAUSAL pair — to rank
a query's keys every one has to be scored — and its operands' bytes, by the
cell's module (lib/index_roofline.py, lib/flops_keye.py), against the chip's
published peaks, over the time the kernel took in the traced window. The
ranking itself (counts over the scores, no product) is time and no work here:
the share says how far the whole kernel is from its products' floor."""

from lib import index_roofline


def read(artifacts):
    return index_roofline.pct(artifacts, "index_scores_roofline")

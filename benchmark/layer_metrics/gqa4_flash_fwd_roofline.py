"""ops: roofline share of the flash forward kernel under grouped-query attention
32 over 4 (``flash_fwd`` on ``[2, 8192, 4096]`` q in the one full layer; k and
v repeated eightfold in front of it): FLOPs of the causal pairs at 128 + 128
lanes a query head, the bytes of q and O at 32 heads and of k and v at the 4 a
grouped kernel could not avoid (lib/flops_mellum.py flash_fwd_cost), over the
time its calls took in the trace (lib/mellum_names.py)."""

from lib import mellum_names


def read(artifacts):
    return mellum_names.flash_fwd_roofline(artifacts)

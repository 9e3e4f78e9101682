"""ops: roofline share of the latent-attention flash dq kernel alone
(``mla_bwd_dq``: scores 192 deep, values 128 wide, 32 heads at 8,192, the shared
rotated key read once a head as it lies in HBM), FLOPs of the causal pairs at
each product's own depth and the operands' bytes (lib/flops_joyai.py
mla_flash_cost), over the time its calls took in the trace
(lib/joyai_names.py)."""

from lib import joyai_names


def read(artifacts):
    return joyai_names.flash_roofline(artifacts, "mla_bwd_dq")

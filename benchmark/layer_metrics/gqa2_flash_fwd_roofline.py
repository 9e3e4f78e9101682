"""ops: roofline share of the flash forward kernel under grouped-query attention
32 over 2 (``flash_fwd`` on ``[2, 8192, 4096]`` q; k and v repeated sixteenfold
in front of it): FLOPs of the causal pairs at 128 + 128 lanes a query head, the
bytes of q and O at 32 heads and of k and v at the 2 a grouped kernel could not
avoid (lib/flops_nemotron.py flash_fwd_cost), over the time its calls took in
the trace (lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.flash_fwd_roofline(artifacts)

"""ops: roofline share of the LOOPED flash backward, ONE kernel of three results
(``flash_bwd``; ``mla_bwd`` at two head sizes: PR 39) — five products a pair the
causal mask keeps, ``2 x pairs x (3 x d + 2 x dv)`` FLOP a head, over the bytes
of q, k, v, O, dO read and dq, dk, dv written once and the ``lse`` rows — by
the cell's module's cost of a call (lib/told.py, lib/flops.py ``bwd``), over
the time the calls took in the traced window. Where the backward is unrolled
(at most 16 block pairs a head) it is two kernels: ``flash_dq_`` /
``flash_dkv_roofline``."""

from lib import told


def read(artifacts):
    return told.kernel_roofline_pct(artifacts, "flash_bwd_roofline")

"""ops: roofline share of the band dkv kernel alone (``swa_bwd_dkv``) at window
1,024: FLOPs of the band's pairs only (``0 <= i - j < 1024``, 2 x pairs x 128
a product) and the bytes its cells read — their own rows and the one
neighbour block's (lib/flops_mellum.py flash_band_cost) — over the time its
calls took in the trace: a kernel that multiplies keys outside the band reads
low, not high (lib/mellum_names.py)."""

from lib import mellum_names


def read(artifacts):
    return mellum_names.swa_roofline(artifacts, "swa_bwd_dkv")

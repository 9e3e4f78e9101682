"""ops: roofline share of the flash fwd kernel alone at CCA's latent shape (8
query heads reading 2 key/value heads of 128, repeated to the query's count in
front of the kernel; the looped side at 8,192), told by the name the program
gives it (``flash_fwd``), FLOPs and bytes from the call's shape as
``flash_fwd_roofline`` counts them (lib/zaya_names.py)."""

from lib import zaya_names


def read(artifacts):
    return zaya_names.flash_roofline(artifacts, "flash_fwd")

"""ops: share of the device's busy time in the three flash kernels (forward,
dq, dkv) at the latent's shape, ``[2, 8192, 1024]`` with 8 / 2 heads of 128,
told by the names the program gives them; ``cca_attn_time_pct`` minus this and
``cca_mix_time_pct`` is the products, rotary and the add (lib/zaya_names.py)."""

from lib import looplm_names, zaya_names


def read(artifacts):
    return zaya_names.pct_under_any(artifacts, looplm_names.FLASH_KERNELS)

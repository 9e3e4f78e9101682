"""ops: share of the device's busy time in the three flash kernels (forward,
dq, dkv) at head_dim 128, told by the names the program gives them;
``looplm_attn_time_pct`` minus this is attention outside its kernels, rotary
included (lib/looplm_names.py)."""

from lib import looplm_names


def read(artifacts):
    return looplm_names.pct_under_any(artifacts, looplm_names.FLASH_KERNELS)

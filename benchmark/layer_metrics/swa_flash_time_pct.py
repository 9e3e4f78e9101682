"""ops: share of the device's busy time in the three windowed flash kernels,
told by the names the program gives them (``swa_fwd``, ``swa_bwd_dq``,
``swa_bwd_dkv``); ``swa_attn_time_pct`` minus this is the window layers'
attention outside its kernels (lib/looplm_names.py)."""

from lib import laguna_names, looplm_names


def read(artifacts):
    return looplm_names.pct_under_any(artifacts, laguna_names.SWA_KERNELS)

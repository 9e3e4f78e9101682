"""ops: share of the device's busy time in the three band kernels at window
1,024, told by the names the program gives them (``swa_fwd``, ``swa_bwd_dq``,
``swa_bwd_dkv``); ``swa1k_attn_time_pct`` minus this is the window layers'
attention outside its kernels (lib/mellum_names.py)."""

from lib import mellum_names


def read(artifacts):
    return mellum_names.pct_under_any(artifacts, mellum_names.SWA_KERNELS)

"""ops: share of the device's busy time in the three band-path kernels
(``swa_fwd``, ``swa_bwd_dq``, ``swa_bwd_dkv``), told by the names the program
gives them; ``band_attn_time_pct`` minus this is the window layers' attention
outside its kernels (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('swa_fwd', 'swa_bwd_dq', 'swa_bwd_dkv'))

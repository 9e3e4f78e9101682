"""ops: share of the device's busy time in the three flash kernels at two
head sizes (scores 192 deep, values 128 wide), ``[2, 8192, 32 x 192]`` against
``[2, 8192, 32 x 128]``, told by the names the program gives them (``mla_fwd``,
``mla_bwd_dq``, ``mla_bwd_dkv``); ``mla_attn_time_pct`` minus this,
``mla_proj_time_pct`` and ``mla_key_rope_time_pct`` is the norm and the add
(lib/joyai_names.py)."""

from lib import joyai_names


def read(artifacts):
    return joyai_names.pct_under_any(artifacts, joyai_names.MLA_KERNELS)

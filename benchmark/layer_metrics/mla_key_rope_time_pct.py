"""ops: share of the device's busy time in what stands between latent
attention's maps and its kernels: ``rope`` (the interleaved rotation of the
last 64 lanes of every q head by the kernels ``rope_fwd`` / ``rope_bwd``, and
of the key's one vector a token) and ``mla_key`` (that vector copied beside
every head's 128 dimensions without positions: k as the kernels read it; its
transpose sums the heads' gradients); every pass (lib/joyai_names.py)."""

from lib import joyai_names


def read(artifacts):
    return joyai_names.pct_under_any(artifacts, joyai_names.KEY_ROPE_SCOPES)

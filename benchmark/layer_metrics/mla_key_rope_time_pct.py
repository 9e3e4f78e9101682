"""ops: share of the device's busy time in what stands between latent
attention's maps and its kernels: ``rope`` (the interleaved rotation of the
last 64 lanes of every q head by the kernels ``rope_fwd`` / ``rope_bwd``, and
of the key's one vector a token) and ``mla_key`` (that vector copied beside
every head's 128 dimensions without positions: k as the kernels read it; its
transpose sums the heads' gradients); every pass (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('rope', 'mla_key'))

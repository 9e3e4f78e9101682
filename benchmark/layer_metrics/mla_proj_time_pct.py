"""ops: share of the device's busy time in latent attention's matrix products
and norms around the kernels — ``mla_down`` (W_qa, W_kva), ``mla_norm`` (the
two latents' RMSNorms), ``mla_up`` (W_qb and the two halves of W_kvb),
``mla_out`` (W_o) — every pass (lib/scope_names.py)."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('mla_down', 'mla_norm', 'mla_up', 'mla_out'))

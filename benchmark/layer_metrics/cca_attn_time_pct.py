"""model: share of the device's busy time under the ``attention`` scope of a
stack whose attention is CCA — the way down into the latent, the convolutions
and the mean, the value shift, the q/k norm, rotary, the flash kernels, the
way back up, the scaled add — every pass of differentiation
(lib/zaya_names.py)."""

from lib import zaya_names


def read(artifacts):
    return zaya_names.pct_under_any(artifacts, ("attention",))

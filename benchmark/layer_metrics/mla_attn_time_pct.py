"""model: share of the device's busy time under the ``attention`` scope of a
stack whose attention is latent (MLA) — the maps into both latents, their
norms, the maps up to the heads, the rotation, the shared key copied to the
heads, the flash kernels at 192 / 128, the way back, the add — in all six
layers (the module's among them), every pass of differentiation
(lib/joyai_names.py)."""

from lib import joyai_names


def read(artifacts):
    return joyai_names.pct_under_any(artifacts, ("attention",))

"""model: share of the device's busy time under the blocks' ``attention`` scope
— the norms, the projections (or a latent mixer's maps), rotary, the residual
add and the flash kernels — in every pass; where it stands in the program's
names is told by the cell's module (lib/told.py). A cell with two attention
kinds has ``full_attn_`` / ``band_attn_time_pct`` in its place."""

from lib import told


def read(artifacts):
    return told.share_pct(artifacts, "attn_time_pct")

"""model: share of the device's busy time under the ``ssm`` scope of NemotronH's
four Mamba-2 sub-layers — the norm, the five input maps, the convolutions, the
scan at 8 groups and 64 chunks of 128 a sequence, the grouped gated norm, the
way back, the add — every pass of differentiation (lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, ("ssm",))

"""model: share of the device's busy time under the ``attention`` scope of the
FULL attention layers' runs (48 query heads over 8 key/value heads of 128,
partial YaRN rotary), every pass of differentiation (lib/laguna_names.py)."""

from lib import laguna_names


def read(artifacts):
    return laguna_names.attention_pct(artifacts, "full_attention")

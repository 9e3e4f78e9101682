"""model: share of the device's busy time in the untied head and the loss over
the 16,384-row slice — ``lm_head_loss``, the fused chunked head on ``[2, 8192,
16384]`` logits (``lm_head`` / ``loss`` where the rule picks full logits) —
every pass (lib/nemotron_names.py)."""

from lib import nemotron_names


def read(artifacts):
    return nemotron_names.pct_under_any(artifacts, nemotron_names.HEAD_SCOPES)

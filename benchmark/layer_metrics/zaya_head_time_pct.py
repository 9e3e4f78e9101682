"""model: share of the device's busy time in the tied head and the loss over
the 32,784-row slice — ``lm_head_loss`` (the fused chunked head, which the
program's shape rule picks at 2 x 8,192), ``lm_head`` / ``loss`` on the
full-logits path — every pass (lib/zaya_names.py)."""

from lib import zaya_names


def read(artifacts):
    return zaya_names.pct_under_any(artifacts, zaya_names.HEAD_SCOPES)

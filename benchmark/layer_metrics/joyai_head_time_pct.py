"""model: share of the device's busy time in the untied head and both losses
over the 16,160-row slice — ``lm_head_loss``, ONE call of the fused chunked head
on the main stack's and the module's states joined along the sequence (32
chunks of 1,024 rows) — every pass (lib/joyai_names.py)."""

from lib import joyai_names


def read(artifacts):
    return joyai_names.pct_under_any(artifacts, joyai_names.HEAD_SCOPES)

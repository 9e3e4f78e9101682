"""model: share of the device's busy time in the language-model head and the
loss — ``lm_head`` / ``tok_emb.attend``, ``loss``, or the fused
``lm_head_loss`` — forward and backward (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.part_pct(artifacts, "head_loss")

"""model: share of the device's busy time in a looped model's heads, exit
gate and loss — ``lm_head_loss`` (the fused head over all passes' states in
one call: the passes share one path and are read together), ``lm_head`` /
``loss`` on the full-logits path, ``exit_gate`` (lib/looplm_names.py)."""

from lib import looplm_names


def read(artifacts):
    return looplm_names.pct_under_any(artifacts, looplm_names.HEAD_AND_GATE)

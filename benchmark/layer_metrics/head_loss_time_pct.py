"""model: share of the device's busy time in the language-model head and the
loss — ``lm_head`` / ``tok_emb.attend`` and ``loss``, or the fused
``lm_head_loss`` (a multi-token-prediction module's head in the same call);
in a looped model all passes' heads and the exit gate — every pass; where it
stands in the program's names is told by the cell's module (lib/told.py)."""

from lib import told


def read(artifacts):
    return told.share_pct(artifacts, "head_loss_time_pct")

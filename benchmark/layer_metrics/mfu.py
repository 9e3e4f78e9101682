"""model: the whole step's share of the chips' peak — tokens per second of this
run's window times the training FLOPs a token needs, recomputation not
counted, over chips times the published bf16 peak (lib/peaks.py). The FLOPs a
token are the configuration's own count, told by its module
(``train_flops_per_token``, lib/told.py): a mixture-of-experts cell counts its
routed experts' products at ZERO rows a token (the steady driver keeps no
counter of its steps), so it reads low, never high, by the margin that
module's docstring states."""

from lib import told


def read(artifacts):
    return told.mfu_pct(artifacts)

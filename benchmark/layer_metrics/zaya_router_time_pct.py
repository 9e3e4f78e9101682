"""ops: share of the device's busy time under ``router`` — the float32
down-projection at highest precision and the state's addition
(``router_eda``), the norm, the three-layer GELU MLP, softmax and the choice
(``router_mlp``) — every pass of differentiation (lib/zaya_names.py)."""

from lib import zaya_names


def read(artifacts):
    return zaya_names.pct_under_any(artifacts, ("router",))

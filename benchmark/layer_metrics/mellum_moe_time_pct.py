"""model: share of the device's busy time in the four expert layers: the
block's ``moe`` scope, from its norm to the add — the float32 softmax router
over 64 experts, the sort and the gathers, the three grouped products over the
16 experts held at 2 rows a token, the weighted sums back; nothing shared —
every pass (lib/mellum_names.py)."""

from lib import mellum_names


def read(artifacts):
    return mellum_names.pct_under_any(artifacts, ("moe",))

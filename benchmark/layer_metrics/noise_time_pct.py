"""model: share of the device's busy time under ``noise``, a diffusion
objective's draws inside the step — a time a block, a mask a token, the
noised tokens and the rows built from both halves — every pass
(lib/scope_names.py); nothing where the program has no such scope."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('noise',))

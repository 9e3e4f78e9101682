"""model: share of the device's busy time under ``attention`` AND ``cross``,
the layers that attend with their own queries to an earlier layer's keys and
values, in every pass (lib/scope_names.py); nothing where the program has no
such scope."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under(artifacts, ('attention',), ('cross',))

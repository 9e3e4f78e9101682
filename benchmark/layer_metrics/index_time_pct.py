"""model: share of the device's busy time a learned index costs: everything
under ``index`` (its three maps, its key's norm, the rotations, the scores and
the top-k, the packed selection) and under ``index_loss`` (its own loss and
that loss's backward), every pass (lib/scope_names.py); nothing where the
program has no such scopes. The attention kernels under the selection are
``flash_time_pct``'s."""

from lib import scope_names


def read(artifacts):
    return scope_names.pct_under_any(artifacts, ('index', 'index_loss'))

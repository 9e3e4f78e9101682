"""trainer: share of the device's busy time under the step's ``optimizer``
scope (``optimizer.update`` and ``apply_updates``) and ``grad_norm``
(lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.part_pct(artifacts, "optimizer")

"""trainer: share of the device's busy time under the step's ``accumulate``
scope — the carry's adds across microbatches and the final scale
(lib/scope_reduce.py). Only a cell that accumulates has it."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.part_pct(artifacts, "accumulate")

"""trainer: share of the device's busy time in operations whose path holds no
scope or module name of the program — only ``jit``, ``jvp``, ``transpose``,
``while``, ``checkpoint`` wrappers, or nothing. The check on the other shares:
what they cannot see (lib/scope_reduce.py)."""

from lib import scope_reduce


def read(artifacts):
    return scope_reduce.part_pct(artifacts, "unscoped")

"""elastic control: the share of the window the account could put to no
cause — its ``unaccounted_s`` between the ``goodput`` snapshots at the
window's edges, over their interval. It guards the tiling: a phase that a
later PR adds and nobody accounts for shows here. Each snapshot holds the
step in flight when it was taken, so the difference is within a step of 0
either way."""

from lib import goodput_events


def read(artifacts):
    return goodput_events.share_pct(
        artifacts, lambda s: s["seconds"]["unaccounted_s"])

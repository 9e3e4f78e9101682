"""elastic control: the share of the window spent on steps that were kept —
the account's ``step_s`` less its ``wasted_s``, differenced between the
``goodput`` snapshots at the window's edges, over their interval. In a job
that is never killed it is 100 less the loop, the input and the saves; one
kill takes the resume AND the steps it threw away."""

from lib import goodput_events


def read(artifacts):
    return goodput_events.share_pct(
        artifacts, lambda s: s["seconds"]["step_s"] - s["wasted_s"])

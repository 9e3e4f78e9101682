"""elastic control: seconds of steps the window's kill threw away — the
account's ``wasted_s`` between the ``goodput`` snapshots at the window's
edges: every step recorded above the step the resume restored, at its own
time on the device. With ``resume_s`` it is what one kill costs."""

from lib import goodput_events


def read(artifacts):
    return goodput_events.over_window(artifacts, lambda s: s["wasted_s"])

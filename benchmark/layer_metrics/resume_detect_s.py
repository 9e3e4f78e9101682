"""elastic control: SIGKILL to the next generation's ``spawn`` in the agent's
timeline — the agent notices the dead worker, the master forms the next
generation, the agent reaps the old process and spawns."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, None, "spawn")

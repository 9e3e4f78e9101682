"""checkpoint: how long the step loop stood still at the save inside the
window (S1, the second periodic save): the time from the saving step's
record to the next step's, less that step's own time. One sample a run."""

from lib import timeline_reduce as tl


def read(artifacts):
    if "records" not in artifacts:
        return None
    return tl.save_stall_s(artifacts["records"], artifacts["save_steps"][-1])

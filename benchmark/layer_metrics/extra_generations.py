"""elastic control: generations spawned beyond the two the schedule expects
(one start, one resume). Each extra one is a reshape nobody asked for."""

from lib import timeline_reduce as tl


def read(artifacts):
    if "timeline" not in artifacts:
        return None
    return tl.extra_generations(artifacts["timeline"])

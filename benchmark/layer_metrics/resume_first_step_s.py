"""worker: ``restored`` to ``first_step_done`` of the resuming generation —
the step program from the compile cache (or compiled) plus one step."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, "restored", "first_step_done")

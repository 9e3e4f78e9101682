"""checkpoint: ``trainer_built`` to ``restored`` of the resuming generation —
agreeing on the step and reading the checkpoint onto the device."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, "trainer_built", "restored")

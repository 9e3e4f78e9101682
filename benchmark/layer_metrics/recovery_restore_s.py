"""checkpoint: ``resume_restore_s`` where the resume lies in set-up and
``recovery_s`` is what it moves — ``trainer_built`` to ``restored`` of the
resuming generation: agreeing on the step and reading the checkpoint onto
the devices, under another mesh than it was saved under where the mix names
one."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, "trainer_built", "restored")

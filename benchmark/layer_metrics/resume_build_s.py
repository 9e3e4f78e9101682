"""worker: ``devices_ready`` to ``trainer_built`` of the resuming generation —
imports of the training stack, mesh, model and Trainer."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, "devices_ready", "trainer_built")

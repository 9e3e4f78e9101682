"""worker: ``spawn`` to ``trainer_built`` of the resuming generation —
interpreter and jax start, the TPU runtime, mesh, model and Trainer."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, "spawn", "trainer_built")

"""worker: ``dist_init_done`` to ``devices_ready`` of the resuming generation —
the first touch of the backend: the TPU runtime's start, with the host's
standstill in it."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, "dist_init_done", "devices_ready")

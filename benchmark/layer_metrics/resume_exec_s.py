"""worker: ``spawn`` to ``worker_main_start`` of the resuming generation —
fork, exec, the interpreter's start and the worker module's own imports."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, "spawn", "worker_main_start")

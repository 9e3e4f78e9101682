"""elastic control: ``resume_detect_s`` where the resume lies in set-up and
``recovery_s`` is what it moves — SIGKILL to the next generation's ``spawn``:
``recovery_reap_s`` + ``recovery_decide_s``."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, None, "spawn")

"""elastic control: ``resume_decide_s`` where the resume lies in set-up and
``recovery_s`` is what it moves — the agent's ``worker_crash`` to the resuming
generation's ``spawn``: the report to the master, its decision (the mesh
policy's pin is read here), the RUN directive."""

from lib import phase_records, timeline_reduce as tl


def read(artifacts):
    reap = phase_records.reap_s(artifacts)
    detect = tl.resume_span_s(artifacts, None, "spawn")
    return detect - reap if reap is not None and detect is not None else None

"""elastic control: the agent's ``worker_crash`` to the resuming generation's
``spawn`` — the report to the master, its decision, the RUN directive and the
agent's own work before the process starts."""

from lib import phase_records, timeline_reduce as tl


def read(artifacts):
    reap = phase_records.reap_s(artifacts)
    detect = tl.resume_span_s(artifacts, None, "spawn")
    return detect - reap if reap is not None and detect is not None else None

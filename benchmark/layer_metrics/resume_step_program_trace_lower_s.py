"""worker: seconds the resuming generation spent tracing and lowering the
step program itself (``trace_s`` + ``lower_s`` of ``train_step`` among
``first_step_done``'s ``programs``): the part of ``resume_trace_lower_s``
that an executable serialized ahead of time would take away."""

from lib import worker_records


def read(artifacts):
    programs = worker_records.resume_programs(artifacts)
    row = programs and programs["rows"].get("train_step")
    return row["trace_s"] + row["lower_s"] if row else None

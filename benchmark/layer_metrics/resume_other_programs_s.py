"""worker: seconds every program but the step's took between ``restored``
and the first step's end — traced, lowered and loaded — which an
executable of the step serialized ahead of time would NOT take away."""

from lib import worker_records


def read(artifacts):
    programs = worker_records.resume_programs(artifacts)
    if programs is None:
        return None
    return programs["other_s"] + sum(
        row["trace_s"] + row["lower_s"] + row["backend_s"]
        for name, row in programs["rows"].items() if name != "train_step")

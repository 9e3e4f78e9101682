"""worker: seconds the resuming generation spent in the backend for its first
step — compiling, or fetching the compiled program from the persistent cache
and loading it (``backend_s`` on ``first_step_done``)."""

from lib import phase_records


def read(artifacts):
    rec = phase_records.of_resume(artifacts, "first_step_done")
    return rec.get("backend_s") if rec else None

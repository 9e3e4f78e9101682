"""entry and set-up: programs the resuming generation had to compile because the
persistent cache did not hold them, up to its first step's end
(``cache_misses`` on ``first_step_done``). Must be 0: generation 1 compiled
the same programs."""

from lib import phase_records


def read(artifacts):
    rec = phase_records.of_resume(artifacts, "first_step_done")
    return rec.get("cache_misses") if rec else None

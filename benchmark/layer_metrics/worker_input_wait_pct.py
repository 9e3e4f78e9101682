"""worker: share of the loop's pace spent waiting for the next batch
(``data_s``: the ``easydl/next_batch`` span). ``loop_overhead_pct`` counts
this wait as the step itself."""

from lib import worker_records


def read(artifacts):
    return worker_records.pace_share_pct(artifacts, ["data_s"])

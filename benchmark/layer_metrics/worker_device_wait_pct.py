"""worker: share of the loop's pace spent in the blocking fetch of the
step's numbers (``wait_s``: the ``easydl/fetch_loss`` span) — the worker's
own count of how device-bound it is."""

from lib import worker_records


def read(artifacts):
    return worker_records.pace_share_pct(artifacts, ["wait_s"])

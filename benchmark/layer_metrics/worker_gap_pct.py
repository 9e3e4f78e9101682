"""worker: share of the loop's pace between one step's fetch returning and
the next step's start (``gap_s``: the record, a save's call, finalize, the
loop-top checks). Sums where ``loop_overhead_pct`` takes medians: the two
agree where no interval stands out."""

from lib import worker_records


def read(artifacts):
    return worker_records.pace_share_pct(artifacts, ["gap_s"])

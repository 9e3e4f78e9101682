"""checkpoint: how much slower the loop's pace is beside an asynchronous
commit — the median interval of in-window pairs whose step began with
``commit_in_flight`` over the median of those without, less 1."""

from lib import worker_records


def read(artifacts):
    return worker_records.commit_drag_pct(artifacts)

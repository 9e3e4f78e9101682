"""worker: share of the loop's pace spent placing the batch on the mesh and
handing the step to the runtime (``shard_s`` + ``dispatch_s``: the
``easydl/shard_batch`` and ``easydl/dispatch`` spans of
``Trainer.train_step``)."""

from lib import worker_records


def read(artifacts):
    return worker_records.pace_share_pct(artifacts,
                                         ["shard_s", "dispatch_s"])

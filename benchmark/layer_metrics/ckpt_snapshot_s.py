"""checkpoint: seconds of the synchronous device-to-host copies of the save
inside the window (S1, at step 2N), as ``CheckpointManager.save`` timed them
(``ckpt_snapshot_done``): the part of ``save_stall_s`` that is the snapshot."""

from lib import phase_records


def read(artifacts):
    rec = phase_records.of_save(artifacts, "ckpt_snapshot_done", -1)
    return rec["seconds"] if rec else None

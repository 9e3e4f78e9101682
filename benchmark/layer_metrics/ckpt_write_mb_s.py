"""checkpoint: megabytes a second at which the committed save (C0, at step N)
wrote its chunks to storage (``bytes / seconds`` of ``ckpt_chunks_written``)."""

from lib import phase_records


def read(artifacts):
    rec = phase_records.of_save(artifacts, "ckpt_chunks_written", 0)
    if rec is None or not rec["seconds"]:
        return None
    return rec["bytes"] / rec["seconds"] / 1e6

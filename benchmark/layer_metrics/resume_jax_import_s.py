"""worker: ``worker_main_start`` to ``jax_imported`` of the resuming
generation — the job file, ``import jax`` and the compile cache's set-up."""

from lib import timeline_reduce as tl


def read(artifacts):
    return tl.resume_span_s(artifacts, "worker_main_start", "jax_imported")

"""worker: seconds the resuming generation spent tracing the step to a jaxpr
and lowering it to MLIR between ``restored`` and its first step's end
(``trace_s`` + ``lower_s`` on ``first_step_done``, from ``jax.monitoring``):
paid before the compile cache can even be asked."""

from lib import phase_records


def read(artifacts):
    rec = phase_records.of_resume(artifacts, "first_step_done")
    if rec is None or "trace_s" not in rec:
        return None
    return rec["trace_s"] + rec["lower_s"]

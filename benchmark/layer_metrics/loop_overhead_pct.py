"""worker: share of the loop's pace that is not the step itself,
1 - median ``step_time_s`` / median interval between step records."""

from lib import timeline_reduce as tl


def read(artifacts):
    if "records" not in artifacts:
        return None
    return tl.loop_overhead_pct(
        artifacts["records"], artifacts["t_open"], artifacts["t_close"],
        artifacts["save_steps"])

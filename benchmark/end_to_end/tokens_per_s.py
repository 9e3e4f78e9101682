"""Training tokens per second, host clock.

A loop in the benchmark's process: steps x tokens per step over the window,
the clock stopped when the last step's loss is on the host. A job in a
worker whose window one generation fills (the kill and the resume in
set-up): the same, by the worker's step records — every step from the record
that opened the window to the window's last, over the seconds between the
two, a save's stall and any other wait among them. A job that is killed
inside its window: tokens per step over the median interval between
consecutive step records, the intervals that hold a save or the kill left
out — the worker loop's own pace."""

from lib import timeline_reduce as tl


def read(artifacts):
    if artifacts.get("one_generation_window"):
        rate = tl.window_steps_per_s(
            artifacts["records"], artifacts["t_open"], artifacts["t_close"])
        return artifacts["tokens_per_step"] * rate if rate else None
    if "records" in artifacts:
        interval = tl.step_interval_s(
            artifacts["records"], artifacts["t_open"], artifacts["t_close"],
            artifacts["save_steps"])
        return artifacts["tokens_per_step"] / interval if interval else None
    return artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]

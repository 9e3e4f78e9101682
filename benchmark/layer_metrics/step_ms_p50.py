"""trainer: median host-clock time of one step in the window, loss fetched."""

import statistics


def read(artifacts):
    steps = artifacts.get("step_s")
    return 1e3 * statistics.median(steps) if steps else None

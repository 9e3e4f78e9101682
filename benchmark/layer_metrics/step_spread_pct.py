"""trainer: how far the slow steps leave the median, p90 / p50 - 1. A host
freeze or a straggling step inside the window shows here."""

import statistics


def read(artifacts):
    steps = sorted(artifacts.get("step_s") or [])
    if len(steps) < 10:
        return None
    p90 = steps[min(len(steps) - 1, int(0.9 * len(steps)))]
    return 100.0 * (p90 / statistics.median(steps) - 1.0)

"""device: share of the traced window in which no operation ran on the
device, 1 - union of the operation intervals / window, averaged over chips."""


def read(artifacts):
    summary = artifacts.get("trace_summary")
    if not summary:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])

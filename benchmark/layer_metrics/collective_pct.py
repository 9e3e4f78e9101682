"""device: share of the traced window in which a collective operation was in
flight on a chip — synchronous ones while they run, asynchronous ones from
their start to the end of their done — averaged over chips."""


def read(artifacts):
    summary = artifacts.get("trace_summary")
    if not summary:
        return None
    return 100.0 * summary["collective_s"] / summary["window_s"]

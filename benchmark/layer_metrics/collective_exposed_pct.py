"""device: share of the traced window in which a collective ran on a chip and
no other operation did — communication that nothing hides."""


def read(artifacts):
    summary = artifacts.get("trace_summary")
    if not summary:
        return None
    return 100.0 * summary["exposed_s"] / summary["window_s"]

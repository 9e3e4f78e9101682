"""ops: share of the device's busy time spent in the Mosaic flash-attention
calls (forward, dq, dkv), found in the trace by the names the compiled step's
own HLO gives them."""


def read(artifacts):
    summary = artifacts.get("trace_summary")
    calls = artifacts.get("flash_calls")
    if not summary or not calls:
        return None
    seconds = sum(summary["ops"].get(c["name"], {}).get("seconds", 0.0)
                  for c in calls)
    return 100.0 * seconds / summary["busy_s"] if seconds else None

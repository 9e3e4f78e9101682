"""ops: the least time the chip could take for the flash calls that ran —
per call the larger of needed causal FLOPs over peak FLOP/s and needed bytes
over peak bytes/s (lib/flops.py, lib/peaks.py; at these shapes compute
bounds all three) — over the time they took in the trace."""

from lib import flops, peaks


def read(artifacts):
    summary = artifacts.get("trace_summary")
    calls = artifacts.get("flash_calls")
    if not summary or not calls:
        return None
    kind = artifacts["device"]["kind"]
    peak_f = peaks.peak(kind, "bf16_flops_per_s")
    peak_b = peaks.peak(kind, "hbm_bytes_per_s")
    least = took = 0.0
    for call in calls:
        ran = summary["ops"].get(call["name"])
        if not ran:
            continue
        cost = flops.flash_causal_cost(call["kind"], call["batch_heads"],
                                       call["seq"], call["head_dim"])
        least += ran["calls"] * flops.roofline_seconds(
            cost["flops"], cost["bytes"], peak_f, peak_b)["seconds"]
        took += ran["seconds"]
    return 100.0 * least / took if took else None

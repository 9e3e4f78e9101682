"""ops: roofline share of the Mamba-1 selective scan's two kernels, told by the
names the program gives them (``sscan_fwd``, ``sscan_bwd``): the element
operations and HBM bytes a token and layer need, forward and backward, from
shapes (the cell's module's ``selective_scan_cost``: lib/flops_phi4flash.py),
against the chip's published bf16 peak and bandwidth, the larger, over the
kernels' time in the traced window — recomputation in the time and not in the
work. The scan is bound by the vector and transcendental units, which have no
published peak: the share reads low."""

from lib import flops, peaks, scope_names, told


def read(artifacts):
    module = told.module_of(artifacts)
    seconds = scope_names.seconds_under(artifacts, (),
                                        ("sscan_fwd", "sscan_bwd"))
    if not seconds or not hasattr(module, "selective_scan_cost"):
        return None
    config, traffic = artifacts["config"], artifacts["traffic"]
    cost = module.selective_scan_cost(config)
    tokens = (traffic["trace_steps"] * traffic["global_batch"]
              * config["kwargs"]["seq_len"] * cost["layers"])
    kind = artifacts["device"]["kind"]
    least = flops.roofline_seconds(
        tokens * cost["flops"], tokens * cost["bytes"],
        peaks.peak(kind, "bf16_flops_per_s"),
        peaks.peak(kind, "hbm_bytes_per_s"))["seconds"]
    return 100.0 * least / seconds

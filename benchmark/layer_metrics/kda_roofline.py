"""ops: roofline share of the delta rule's recurrence: the FLOPs and HBM bytes
the MATHEMATICS of the rule needs a token and layer, forward and backward,
from shapes (the cell's module's ``kda_cost``: lib/flops_kimi_linear.py — no
chunk length in it), against the chip's published bf16 peak and bandwidth,
the larger, over the time spent under ``kda`` in the traced window —
recomputation in the time and not in the work. The chunk form spends more
FLOPs than the recurrence to reach the MXU, and its diagonal sub-blocks are
bound by the vector and transcendental units, which have no published peak:
the share reads low. A program without the scope, or a cell whose module
states no ``kda_cost``, gives nothing to read."""

from lib import flops, peaks, scope_names, told


def read(artifacts):
    module = told.module_of(artifacts)
    seconds = scope_names.seconds_under(artifacts, (), ("kda",))
    if not seconds or not hasattr(module, "kda_cost"):
        return None
    config, traffic = artifacts["config"], artifacts["traffic"]
    cost = module.kda_cost(config)
    tokens = (traffic["trace_steps"] * traffic["global_batch"]
              * config["kwargs"]["seq_len"] * cost["layers"])
    kind = artifacts["device"]["kind"]
    least = flops.roofline_seconds(
        tokens * cost["flops"], tokens * cost["bytes"],
        peaks.peak(kind, "bf16_flops_per_s"),
        peaks.peak(kind, "hbm_bytes_per_s"))["seconds"]
    return 100.0 * least / seconds

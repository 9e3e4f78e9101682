"""model: model FLOP/s utilisation by the looped count — tokens per second of
this run times the training FLOPs a token needs (a pass is 6 a layer and head
parameter plus 12 x width x sequence a layer; times the passes:
lib/flops_looplm.py; recomputation not counted), over chips times the
published bf16 peak (lib/peaks.py)."""

from lib import flops_looplm, peaks


def read(artifacts):
    # Off the chip there is no peak to hold a rate against (and a TPU of a
    # kind the table lacks is an error, raised below).
    if "step_s" not in artifacts or artifacts["device"]["platform"] != "tpu":
        return None
    config = artifacts["config"]
    if "total_ut_steps" not in config:
        return None
    per_token = flops_looplm.train_flops_per_token(
        config, config["kwargs"]["seq_len"])
    rate = artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]
    peak = peaks.peak(artifacts["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * rate * per_token / (artifacts["chips"] * peak)

"""model: model FLOP/s utilisation by the hybrid's own count — tokens per
second of this run times the training FLOPs a token needs (6 a parameter,
12 x width x sequence for each attention layer, three forwards of the scan
for each Mamba-2 layer: lib/flops_ssd.py; recomputation not counted), over
chips times the published bf16 peak (lib/peaks.py)."""

from lib import flops_ssd, peaks


def read(artifacts):
    # Off the chip there is no peak to hold a rate against (and a TPU of a
    # kind the table lacks is an error, raised below).
    if "step_s" not in artifacts or artifacts["device"]["platform"] != "tpu":
        return None
    config = artifacts["config"]
    if "layer_types" not in config:
        return None
    per_token = flops_ssd.hybrid_train_flops_per_token(
        artifacts["n_params"], config, config["kwargs"]["seq_len"])
    rate = artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]
    peak = peaks.peak(artifacts["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * rate * per_token / (artifacts["chips"] * peak)

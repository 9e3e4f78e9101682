"""model: model FLOP/s utilisation by Laguna's ACTIVE count — tokens per second
of this run times the training FLOPs a token needs here (6 a parameter of the
matrix products; 12 a pair and head dimension the causal mask or the window's
band keeps: lib/flops_laguna.py; recomputation not counted), over chips times
the published bf16 peak (lib/peaks.py). The routed experts' products count at
ZERO rows a token: the rows that land here drift over the window (0.0-1.7 a
token) and the steady driver keeps no counter of its steps, so the share reads
up to 5% low (a row a token and sparse layer is 75 MFLOP of 2.4 GFLOP), never
high."""

from lib import flops_laguna, peaks


def read(artifacts):
    # Off the chip there is no peak to hold a rate against (and a TPU of a
    # kind the table lacks is an error, raised below).
    if "step_s" not in artifacts or artifacts["device"]["platform"] != "tpu":
        return None
    config = artifacts["config"]
    if "mlp_layer_types" not in config:
        return None
    per_token = flops_laguna.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)
    rate = artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]
    peak = peaks.peak(artifacts["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * rate * per_token / (artifacts["chips"] * peak)

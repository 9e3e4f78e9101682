"""model: model FLOP/s utilisation by ZAYA1's ACTIVE count — tokens per second of
this run times the training FLOPs a token needs here (6 a parameter of the
matrix products; 12 a pair and head dimension the causal mask keeps:
lib/flops_zaya.py; recomputation not counted), over chips times the published
bf16 peak (lib/peaks.py). The routed experts' products count at ZERO rows a
token, as ``moe_model_flops_util`` counts them: the steady driver keeps no
counter of its steps. At the seed's 8 / 17 rows a token and layer they are 213
of 1,143 MFLOP a token in the cell, so the share reads 19% of itself low (the
products' share), never high."""

from lib import flops_zaya, peaks, zaya_names


def read(artifacts):
    # Off the chip there is no peak to hold a rate against (and a TPU of a
    # kind the table lacks is an error, raised below).
    if "step_s" not in artifacts or artifacts["device"]["platform"] != "tpu" \
            or not zaya_names.is_zaya(artifacts):
        return None
    config = artifacts["config"]
    per_token = flops_zaya.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)
    rate = artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]
    peak = peaks.peak(artifacts["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * rate * per_token / (artifacts["chips"] * peak)

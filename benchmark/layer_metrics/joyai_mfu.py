"""model: model FLOP/s utilisation by JoyAI-LLM's ACTIVE count — tokens per
second of this run times the training FLOPs a token needs here (6 a parameter
of the matrix products, the head twice: the main stack's and the module's; 6 x
(192 + 128) a pair and head the causal mask keeps in each of the six attention
layers: lib/flops_joyai.py; recomputation not counted), over chips times the
published bf16 peak (lib/peaks.py). The routed experts' products count at ZERO
rows a token, as ``moe_model_flops_util`` and ``zaya_model_flops_util`` count
them: the steady driver keeps no counter of its steps. At the seed's 0.5 rows a
token and sparse layer they are 71 of 3,399 MFLOP a token in the cell, so the
share reads under 2% of itself low, never high."""

from lib import flops_joyai, joyai_names, peaks


def read(artifacts):
    # Off the chip there is no peak to hold a rate against (and a TPU of a
    # kind the table lacks is an error, raised below).
    if "step_s" not in artifacts or artifacts["device"]["platform"] != "tpu" \
            or not joyai_names.is_joyai(artifacts):
        return None
    config = artifacts["config"]
    per_token = flops_joyai.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)
    rate = artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]
    peak = peaks.peak(artifacts["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * rate * per_token / (artifacts["chips"] * peak)

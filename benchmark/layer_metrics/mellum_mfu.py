"""model: model FLOP/s utilisation by Mellum 2's ACTIVE count — tokens per
second of this run times the training FLOPs a token needs here (6 a parameter
of the matrix products: attention's four, the routers at 64 outputs, the head
once; 6 x 2 x 128 a pair and query head the mask keeps: the causal triangle in
the full layer, the band of 1,024 keys in the three window layers:
lib/flops_mellum.py; recomputation not counted), over chips times the published
bf16 peak (lib/peaks.py). The routed experts' products count at ZERO rows a
token, as ``moe_model_flops_util``, ``joyai_mfu`` and ``nemotron_mfu`` count
them: the steady driver keeps no counter of its steps. At the cut's 2 rows a
token and layer they are 297 of 1,493 MFLOP a token in the cell, so the share
reads a fifth of itself low — the most of any cell — never high."""

from lib import flops_mellum, mellum_names, peaks


def read(artifacts):
    # Off the chip there is no peak to hold a rate against (and a TPU of a
    # kind the table lacks is an error, raised below).
    if "step_s" not in artifacts or artifacts["device"]["platform"] != "tpu" \
            or not mellum_names.is_mellum(artifacts):
        return None
    config = artifacts["config"]
    per_token = flops_mellum.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)
    rate = artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]
    peak = peaks.peak(artifacts["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * rate * per_token / (artifacts["chips"] * peak)

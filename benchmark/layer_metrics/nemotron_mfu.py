"""model: model FLOP/s utilisation by NemotronH's ACTIVE count — tokens per
second of this run times the training FLOPs a token needs here (6 a parameter
of the matrix products: the Mamba-2 mixers' input and output maps, attention's
four, the routers at 128 outputs, the shared experts' two matrices, the head
once; 6 x 2 x 128 a pair and query head the causal mask keeps in the one
attention sub-layer; three forwards of the scan at 8 groups and chunks of 128
in each of the four M sub-layers: lib/flops_nemotron.py; recomputation not
counted), over chips times the published bf16 peak (lib/peaks.py). The routed
experts' products count at ZERO rows a token, as ``joyai_mfu`` counts them: the
steady driver keeps no counter of its steps. At the seed's 0.375 rows a token
and E sub-layer they are 90 of 2,153 MFLOP a token in the cell, so the share
reads 4% of itself low, never high."""

from lib import flops_nemotron, nemotron_names, peaks


def read(artifacts):
    # Off the chip there is no peak to hold a rate against (and a TPU of a
    # kind the table lacks is an error, raised below).
    if "step_s" not in artifacts or artifacts["device"]["platform"] != "tpu" \
            or not nemotron_names.is_nemotron(artifacts):
        return None
    config = artifacts["config"]
    per_token = flops_nemotron.train_flops_per_token(
        config, config["kwargs"]["seq_len"], rows_per_token=0.0)
    rate = artifacts["steps"] * artifacts["tokens_per_step"] \
        / artifacts["window_s"]
    peak = peaks.peak(artifacts["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * rate * per_token / (artifacts["chips"] * peak)

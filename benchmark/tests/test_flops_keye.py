"""``lib/flops_keye.py`` against ISSUE 61's hand count of one chip's share of
Keye-VL-2.0-30B-A3B's language model at 16,384 tokens."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, flops_keye, flops_sdar


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


def test_parameters_held(config):
    assert flops_keye.attention_products(config) == 18_874_368
    assert flops_keye.index_products(config) == 2_260_992 \
        == 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert flops_keye.expert_params(config) == 4_718_592
    # SDAR's layer and the index: its three maps and its key's LayerNorm
    assert flops_keye.layer_params(config) == 94_638_336 + 2_260_992 + 128 \
        == 96_899_456
    assert flops_keye.param_count(config) == 659_190_016
    assert round(flops_keye.param_count(config) * 18 / 1e9, 2) == 11.87


@pytest.mark.parametrize("seq,topk", [(8, 3), (64, 16), (64, 64), (64, 100)])
def test_pairs_counted_one_by_one(seq, topk):
    assert flops_keye.selected_pairs(seq, topk) == sum(
        min(t + 1, topk) for t in range(seq))
    assert flops_keye.causal_pairs(seq) == sum(t + 1 for t in range(seq))


def test_pairs_at_the_cells_length():
    chosen = flops_keye.selected_pairs(16384, 2048)
    causal = flops_keye.causal_pairs(16384)
    assert chosen == 2048 * 2049 // 2 + (16384 - 2048) * 2048 == 31_458_304
    assert causal == 134_225_920
    assert round(100 * chosen / causal, 1) == 23.4
    assert round(100 * flops_keye.selected_pairs(8192, 2048)
                 / flops_keye.causal_pairs(8192), 1) == 43.7


def test_train_flops_a_token(config):
    seq = 16384
    chosen, causal = 31_458_304 / seq, 134_225_920 / seq
    products = 6 * (18_874_368 + 2048 * 128) + 18992 * 2048
    want = 6.0 * products + 6 * (
        4.0 * 2_260_992 + 6.0 * 32 * 256 * chosen
        + 1024 * (2.0 * causal + 4.0 * chosen))
    assert flops_keye.train_flops_per_token(config, seq, 0.0) \
        == pytest.approx(want)
    routed = flops_keye.train_flops_per_token(config, seq, 1.0) - want
    assert routed == pytest.approx(6.0 * 6 * 4_718_592)
    # the numbers the cell's module quotes: MFLOP a token
    assert round(want / 1e6) == 1691 and round(routed / 1e6) == 170
    # a token's selected scores, a layer: 94 MFLOP where SDAR's mask keeps
    # 8,196 keys a row at twice the rows
    assert round(6.0 * 32 * 256 * chosen / 1e6) == 94
    # a step: 16,384 tokens, about 30 TFLOP of model FLOPs
    assert round((want + routed) * seq / 1e12, 1) == 30.5


def test_an_attention_call_under_the_selection(config):
    """``4 d`` FLOP a SELECTED pair and head forward, ``10 d`` backward; q
    and O at 32 heads, k and v at 4, and the selection a bit a pair."""
    fwd = flops_keye.flash_selected_cost("fwd", 1, 16384, 32, 4, 128, 2048)
    assert fwd["flops"] == 32 * 31_458_304 * 4 * 128
    assert fwd["bytes"] == 16384 * (2 * 36 * 128 * 2 + 32 * 4) \
        + 16384 * 16384 // 8
    bwd = flops_keye.flash_selected_cost("bwd", 1, 16384, 32, 4, 128, 2048)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    causal = flops.flash_gqa_cost("fwd", 1, 16384, 32, 4, 128)
    assert round(fwd["flops"] / causal["flops"], 3) == 0.234
    least = flops.roofline_seconds(fwd["flops"], fwd["bytes"], 197e12, 819e9)
    assert least["bound"] == "compute" and round(least["seconds"], 4) == 0.0026
    # fewer rows than topk: every causal pair, a causal call's FLOPs
    assert flops_keye.flash_selected_cost(
        "fwd", 2, 1024, 32, 4, 128, 2048)["flops"] \
        == flops.flash_gqa_cost("fwd", 2, 1024, 32, 4, 128)["flops"]


def test_the_index_kernels_by_the_pairs_the_mathematics_needs(config):
    ranked = flops_keye.index_select_cost(config, 16384)
    assert ranked["flops"] == 2.0 * 16 * 64 * 134_225_920
    assert ranked["bytes"] == 16384 * (17 * 64 * 2 + 16 * 4 + 8) \
        + 16384 * 16384 // 8
    assert round(ranked["flops"] / 1e9) == 275
    own = flops_keye.index_loss_cost(config, 16384)
    assert own["flops"] == (2.0 * 32 * 128 + 6.0 * 16 * 64) * 31_458_304
    assert own["bytes"] == 16384 * (2 * (17 * 64 * 2 + 64) + 36 * 128 * 2
                                    + 32 * 4 + 8) + 16384 * 16384 // 8
    for cost in (ranked, own):
        least = flops.roofline_seconds(cost["flops"], cost["bytes"], 197e12,
                                       819e9)
        assert least["bound"] == "compute"


def test_the_share_beside_sdars(config):
    """The cut is SDAR's plus the index: the same attention, router, experts
    and slice."""
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        sdar = json.load(f)
    assert flops_keye.param_count(config) - flops_sdar.param_count(sdar) \
        == 6 * (2_260_992 + 128)
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "vocab_size",
                "router_width", "layer_types"):
        assert config[key] == sdar[key], key

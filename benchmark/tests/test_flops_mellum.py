"""Hand counts for ``lib/flops_mellum.py`` at the published widths — the
benchmark's share (four layers, 16 of 64 experts, a quarter of the
vocabulary) and the model as published — and agreement with the program's
own count (``TransformerConfig``)."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, flops_mellum


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json")) as f:
        return json.load(f)


def test_layers_and_runs(config):
    assert flops_mellum.layers(config) == [
        ("sliding_attention", "sparse")] * 3 + [("full_attention", "sparse")]
    assert flops_mellum.runs(config) == [
        ("sliding_attention", "sparse", 3), ("full_attention", "sparse", 1)]


def test_the_parts_by_hand(config):
    # q and o 2 x 9.44M, k and v 2 x 1.18M
    assert flops_mellum.attention_params(config) \
        == 2 * 2304 * 4096 + 2 * 2304 * 512 == 21_233_664
    assert flops_mellum.expert_params(config) == 3 * 2304 * 896 == 6_193_152
    # router 0.15M + 16 experts 99.09M; nothing shared
    assert flops_mellum.expert_layer_products(config, 16) \
        == 147_456 + 99_090_432
    assert flops_mellum.expert_layer_products(config, 0.0) == 147_456


def test_parameters_of_the_share_and_of_the_published_model(config):
    d = 2304
    layer = 21_233_664 + 2 * d + 147_456 + 99_090_432
    assert layer == 120_476_160
    assert flops_mellum.param_count(config) \
        == 2 * 24576 * d + d + 4 * layer == 595_153_152
    # as published: 28 layers, 64 experts, 98,304 rows: "12B"
    published = dict(
        config, num_experts=64, vocab_size=98304,
        layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 7,
        mlp_layer_types=["sparse"] * 28)
    assert round(flops_mellum.param_count(published) / 1e7) == 1215


def test_pairs_the_masks_keep():
    assert flops_mellum.seen_pairs(8192) == 8192 * 8193 // 2
    # each of the first 1,024 queries sees i + 1 keys, every later one 1,024
    assert flops_mellum.seen_pairs(8192, 1024) \
        == 1024 * 1025 // 2 + (8192 - 1024) * 1024 == 7_864_832
    assert flops_mellum.seen_pairs(512, 1024) == 512 * 513 // 2
    # written out
    assert flops_mellum.seen_pairs(10, 3) == sum(
        1 for i in range(10) for j in range(10) if 0 <= i - j < 3)


def test_active_flops_a_token(config):
    seq = 8192
    full = 12 * 32 * 128 * (seq + 1) / 2           # a token's causal pairs
    band = 12 * 32 * 128 * 7_864_832 / seq
    active = 147_456 + 2.0 * 6_193_152
    per_token = 6 * 24576 * 2304 + 4 * 6 * (21_233_664 + active) \
        + 3 * band + full
    assert flops_mellum.train_flops_per_token(config, seq, 2.0) \
        == pytest.approx(per_token)
    # 1,493 MFLOP a token, 24.5 TFLOP a step of 16,384 tokens
    assert per_token == pytest.approx(1.493e9, rel=1e-3)
    # the routed experts' rows are a fifth of it: what the MFU's reader,
    # which counts them at zero rows, leaves out
    none = flops_mellum.train_flops_per_token(config, seq, 0.0)
    assert per_token - none == pytest.approx(4 * 6 * 2 * 6_193_152)
    assert (per_token - none) / per_token == pytest.approx(0.199, abs=0.001)
    # forward, a token and period (ISSUE 45): projections 170 MFLOP, held
    # experts 99, router 1, the head 113; scores and values by the pairs
    assert 4 * 2 * 21_233_664 == pytest.approx(170e6, rel=0.01)
    assert 4 * 2 * 2 * 6_193_152 == pytest.approx(99e6, rel=0.01)
    assert 2 * 24576 * 2304 == pytest.approx(113e6, rel=0.01)
    assert (3 * band + full) / 3 == pytest.approx(114.5e6, rel=0.01)


def test_the_programs_own_count_agrees(config):
    from easydl_tpu.models.mellum import describe

    cfg = describe(**config["kwargs"])
    assert cfg.param_count == flops_mellum.param_count(config)
    # the program counts a full layer's scores in full (12 x width x
    # sequence, the convention of its other models) and a window layer's by
    # its band of `window` keys a token: this file counts both by the pairs
    # the mask keeps; the program counts the norms' gains as parameters too
    mine = flops_mellum.train_flops_per_token(config, 8192, 2.0)
    theirs = cfg.train_flops_per_token(8192)
    convention = 12 * 32 * 128 * (8192 - 8193 / 2) \
        + 3 * 12 * 32 * 128 * (1024 - 7_864_832 / 8192)
    assert theirs - mine == pytest.approx(
        convention + 6 * 4 * 2 * 2304 + 6 * 2304, rel=1e-6)


def test_band_cost_of_a_windowed_flash_call(config):
    cost = flops_mellum.flash_band_cost("fwd", 2, 8192, 32 * 128, 128, 1024)
    assert cost["flops"] == 2 * 2 * 2.0 * 7_864_832 * 4096
    # q, k, v, O once and k, v half again (1,024 rows beside 2,048); lse
    assert cost["bytes"] == 2 * (5 * 8192 * 4096 * 2 + 8192 * 32 * 4)
    assert flops_mellum.flash_band_cost(
        "fwd", 2, 8192, 4096, 128, 1024, neighbour=0.0)["bytes"] \
        == 2 * (4 * 8192 * 4096 * 2 + 8192 * 32 * 4)
    # the whole triangle is 4.27 times the band: a kernel that visited it
    # would read a quarter of its roofline share
    whole = flops.flash_causal_cost("fwd", 2, 8192, 32 * 128)["flops"]
    assert whole / cost["flops"] == pytest.approx(4.27, abs=0.01)
    for kind, matmuls, mats in (("dq", 3, 5 + 1.0), ("dkv", 4, 6 + 1.5)):
        other = flops_mellum.flash_band_cost(kind, 2, 8192, 4096, 128, 1024)
        assert other["flops"] == matmuls / 2 * cost["flops"]
        assert other["bytes"] == 2 * (mats * 8192 * 4096 * 2
                                      + 2 * 8192 * 32 * 4)
    # compute sets every band kernel's roofline at these shapes
    for kind in ("fwd", "dq", "dkv"):
        c = flops_mellum.flash_band_cost(kind, 2, 8192, 4096, 128, 1024)
        assert flops.roofline_seconds(c["flops"], c["bytes"], 197e12,
                                      819e9)["bound"] == "compute"


def test_flash_forward_cost_under_grouped_queries():
    cost = flops.flash_gqa_cost("fwd", 2, 8192, 32, 4, 128)
    assert cost["flops"] == 2 * 32 * 2.0 * (8192 * 8193 // 2) * 256
    assert cost["bytes"] == 2 * 8192 * (2 * 36 * 128 * 2 + 32 * 4)

"""Hand counts for ``lib/flops_laguna.py`` at the published widths — the
benchmark's share (five layers, 32 of 256 experts, an eighth of the
vocabulary) and the model as published — and agreement with the program's
own count (``TransformerConfig``)."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, flops_laguna


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "laguna-xs.2.json")) as f:
        return json.load(f)


def test_layers_and_runs(config):
    assert flops_laguna.layers(config) == [
        ("full_attention", "dense", 48), ("sliding_attention", "sparse", 64),
        ("sliding_attention", "sparse", 64),
        ("sliding_attention", "sparse", 64), ("full_attention", "sparse", 48)]
    assert flops_laguna.runs(config) == [
        ("full_attention", "dense", 1), ("sliding_attention", "sparse", 3),
        ("full_attention", "sparse", 1)]


def test_the_parts_by_hand(config):
    # attention 48 heads 29.36M + gate 0.10M; 64 heads 37.75M + 0.13M
    assert flops_laguna.attention_params(config, 48) == 29_360_128 + 98_304
    assert flops_laguna.attention_params(config, 64) == 37_748_736 + 131_072
    assert flops_laguna.expert_params(config) == 3 * 2048 * 512 == 3_145_728
    assert flops_laguna.ffn_params(config, "dense", 0) == 50_331_648
    # router 0.52M + shared 3.15M + 32 experts 100.66M
    assert flops_laguna.ffn_params(config, "sparse", 32) \
        == 524_288 + 3_145_728 + 100_663_296


def test_parameters_of_the_share_and_of_the_published_model(config):
    d = 2048
    layer0 = 29_360_128 + 98_304 + 50_331_648 + 2 * d
    window = 37_748_736 + 131_072 + 104_333_312 + 2 * d
    layer4 = 29_360_128 + 98_304 + 104_333_312 + 2 * d
    assert flops_laguna.param_count(config) \
        == 2 * 12544 * d + d + layer0 + 3 * window + layer4 == 691_623_936
    # as published: 40 layers, 256 experts, 100,352 rows: "33.4B"
    published = dict(
        config, num_experts=256, vocab_size=100352,
        layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 10,
        mlp_layer_types=["dense"] + ["sparse"] * 39)
    assert round(flops_laguna.param_count(published) / 1e7) == 3344


def test_pairs_the_masks_keep():
    assert flops_laguna.seen_pairs(8192) == 8192 * 8193 // 2
    # each of the first 512 queries sees i + 1 keys, every later one 512
    assert flops_laguna.seen_pairs(8192, 512) \
        == 512 * 513 // 2 + (8192 - 512) * 512 == 4_063_488
    assert flops_laguna.seen_pairs(256, 512) == 256 * 257 // 2
    # written out
    assert flops_laguna.seen_pairs(10, 3) == sum(
        1 for i in range(10) for j in range(10) if 0 <= i - j < 3)


def test_active_flops_a_token(config):
    seq = 8192
    full = 12 * 48 * 128 * (seq + 1) / 2           # a token's causal pairs
    band = 12 * 64 * 128 * 4_063_488 / seq
    active_sparse = 524_288 + 3_145_728 + 1.0 * 3_145_728
    per_token = (6 * 12544 * 2048
                 + 6 * (29_458_432 + 50_331_648) + full
                 + 3 * (6 * (37_879_808 + active_sparse) + band)
                 + 6 * (29_458_432 + active_sparse) + full)
    assert flops_laguna.train_flops_per_token(config, seq, 1.0) \
        == pytest.approx(per_token)
    # 3 x the issue's forward count of about 0.80 GFLOP a token
    assert 2.3e9 < per_token < 2.5e9
    # the rows routed move it: two rows a token add one expert a sparse layer
    assert flops_laguna.train_flops_per_token(config, seq, 2.0) - per_token \
        == pytest.approx(4 * 6 * 3_145_728)
    # the experts' share here (13%) against the whole model's (38%)
    window_fwd = (2 * (37_879_808 + active_sparse) + band / 3)
    assert 2 * (active_sparse) / window_fwd == pytest.approx(0.13, abs=0.02)


def test_the_programs_own_count_agrees(config):
    from easydl_tpu.models.laguna import describe

    cfg = describe(**config["kwargs"])
    assert cfg.param_count == flops_laguna.param_count(config)
    # the program counts a full layer's scores in full (12 x width x
    # sequence, the convention of its other models) and a window layer's by
    # its band of `window` keys a token: this file counts both by the pairs
    # the mask keeps
    mine = flops_laguna.train_flops_per_token(config, 8192, 1.0)
    theirs = cfg.train_flops_per_token(8192)
    convention = 2 * 12 * 48 * 128 * (8192 - 8193 / 2) \
        + 3 * 12 * 64 * 128 * (512 - 4_063_488 / 8192)
    assert theirs - mine == pytest.approx(convention + 6 * 5 * 2 * 2048 + 6 * 2048,
                                          rel=1e-6)


def test_band_cost_of_a_windowed_flash_call(config):
    cost = flops_laguna.flash_band_cost("fwd", 2, 8192, 64 * 128, 128, 512)
    assert cost["flops"] == 2 * 2 * 2.0 * 4_063_488 * 8192
    assert cost["bytes"] == 2 * (4 * 8192 * 8192 * 2 + 8192 * 64 * 4)
    # the whole triangle is 8.26 times the band: a kernel that visited it
    # would read an eighth of its roofline share
    whole = flops.flash_causal_cost("fwd", 2, 8192, 64 * 128)["flops"]
    assert whole / cost["flops"] == pytest.approx(8.26, abs=0.01)
    for kind, matmuls in (("dq", 3), ("dkv", 4)):
        assert flops_laguna.flash_band_cost(kind, 2, 8192, 8192, 128, 512)[
            "flops"] == matmuls / 2 * cost["flops"]

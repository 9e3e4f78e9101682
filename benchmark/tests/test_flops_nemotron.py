"""lib/flops_nemotron.py by hand at the cell's numbers and against the
program's own description (``TransformerConfig.param_count``,
``train_flops_per_token``): the counts agree where they count the same thing
and differ by what the docstrings say."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, flops_nemotron, flops_ssd
from lib.flops_laguna import seen_pairs


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def test_the_share_by_hand(config):
    d = 2688
    mixer_products = d * (4096 + 4096 + 1024 + 1024 + 64) + 4096 * d
    assert flops_nemotron.mamba_products(config) == mixer_products \
        == 38_707_200
    assert flops_nemotron.mamba_params(config) \
        == mixer_products + 5 * 6144 + 3 * 64 + 4096 + d == 38_744_896
    assert flops_nemotron.attention_products(config) \
        == 2 * d * 4096 + 2 * d * 256 == 23_396_352
    assert flops_nemotron.expert_params(config) == 2 * d * 1856 == 9_977_856
    assert flops_nemotron.expert_layer_products(config, 8) \
        == d * 128 + 2 * d * 3712 + 8 * 9_977_856 == 100_122_624
    assert flops_nemotron.param_count(config) == 666_963_456 == (
        4 * 38_744_896 + (23_396_352 + d) + 4 * (100_122_624 + 128 + d)
        + 2 * 16384 * d + d)


def test_the_counts_are_the_programs(config):
    from easydl_tpu.models.nemotron_h import describe

    cfg = describe(**config["kwargs"])
    assert flops_nemotron.param_count(config) == cfg.param_count
    # the program's convention: 6 a parameter whatever it is (norms,
    # convolutions, per-head leaves, the bias), the scores in full; this
    # file's: the matrix products alone, the causal pairs
    seq = config["kwargs"]["seq_len"]
    mine = flops_nemotron.train_flops_per_token(config, seq, 0.375)
    not_products = (4 * (flops_nemotron.mamba_params(config)
                         - flops_nemotron.mamba_products(config))
                    + 2688 + 4 * (128 + 2688) + 2688)
    scores_full = 12.0 * 32 * 128 * seq
    scores_seen = 6.0 * 32 * 256 * seen_pairs(seq) / seq
    assert cfg.train_flops_per_token(seq) == pytest.approx(
        mine + 6.0 * not_products + scores_full - scores_seen, rel=1e-6)
    assert mine < cfg.train_flops_per_token(seq)
    # the routed rows as none: what ``mfu`` reads in the cell with
    none = flops_nemotron.train_flops_per_token(config, seq, 0.0)
    assert mine - none == pytest.approx(6.0 * 4 * 0.375 * 9_977_856)
    assert 0.04 < (mine - none) / mine < 0.045
    assert none == pytest.approx(2.063e9, rel=1e-3)


def test_the_scan_at_eight_groups_and_chunks_of_128(config):
    shape = flops_nemotron.ssd_shape(config)
    assert shape == {"n_heads": 64, "head_dim": 64, "d_state": 128,
                     "n_groups": 8, "chunk": 128}
    # the scores once a GROUP: 2 x 128 x 128 x 8; inside the chunk 2 x 128 x
    # 64 a head; the two state products 4 x 64 x 128 a head
    assert flops_ssd.ssd_forward_flops_per_token(**shape) \
        == 262_144 + 1_048_576 + 2_097_152
    cost = flops_nemotron.ssd_train_cost_per_token(config)
    assert cost["flops"] == 3 * 3_407_872
    # x and y at 4096 lanes, B and C at 8 x 128, dt float32 a head
    assert cost["bytes"] == (2 * 8192 + 2 * 2048 + 256) \
        + (3 * 8192 + 4 * 2048 + 512)


def test_the_flash_forward_under_grouped_queries():
    cost = flops.flash_gqa_cost("fwd", 2, 8192, 32, 2, 128)
    pairs = 8192 * 8193 // 2
    assert cost["flops"] == 2 * 32 * 2.0 * pairs * 256
    assert cost["bytes"] == 2 * 8192 * (2 * 34 * 128 * 2 + 32 * 4)
    # compute-bound by far on a v5e: 1.1 TFLOP (5.6 ms) against 0.29 GB
    # (0.35 ms)
    assert cost["flops"] / 197e12 > 10 * cost["bytes"] / 819e9

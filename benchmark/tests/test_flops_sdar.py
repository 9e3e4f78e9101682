"""``lib/flops_sdar.py`` against ISSUE 51's hand count of one chip's share of
SDAR-30B-A3B-Chat trained by block diffusion."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, flops_sdar


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


def test_parameters_held(config):
    assert flops_sdar.attention_products(config) == 18_874_368
    assert flops_sdar.expert_params(config) == 4_718_592
    assert flops_sdar.layer_params(config) == 94_638_336
    assert flops_sdar.param_count(config) == 645_623_296


@pytest.mark.parametrize("seq,block", [(8, 4), (64, 4), (64, 32), (8192, 4)])
def test_live_pairs(seq, block):
    """``L² + L B`` of ``4 L²``; counted pair by pair where that is small."""
    assert flops_sdar.live_pairs(seq, block) == seq * seq + seq * block
    if seq <= 64:
        count = 0
        for q in range(2 * seq):
            for k in range(2 * seq):
                bq, bk = (q % seq) // block, (k % seq) // block
                count += (bk == bq if k < seq else bk < bq) if q < seq \
                    else (k >= seq and bk <= bq)
        assert count == flops_sdar.live_pairs(seq, block)
    # half of what a causal mask over 2 L rows keeps, and a block's worth
    causal = 2 * seq * (2 * seq + 1) // 2
    assert 2 * flops_sdar.live_pairs(seq, block) \
        == causal + seq * (2 * block - 1)


def test_train_flops_a_data_token(config):
    seq = 8192
    products = 2 * 6 * (18_874_368 + 2048 * 128) + 18992 * 2048
    pairs = 6 * (8192 + 4)  # six layers, L + B keys a token
    want = 6.0 * products + 6.0 * 32 * 256 * pairs
    assert flops_sdar.train_flops_per_token(config, seq, 0.0) \
        == pytest.approx(want)
    routed = flops_sdar.train_flops_per_token(config, seq, 1.0) - want
    assert routed == pytest.approx(6.0 * 2 * 6 * 4_718_592)
    # the numbers the cell's module quotes: MFLOP a data token
    assert round(want / 1e6) == 4028 and round(routed / 1e6) == 340
    # a step: 8,192 data tokens, about 36 TFLOP
    assert round((want + routed) * seq / 1e12, 1) == 35.8


def test_a_call_under_the_block_mask(config):
    """``4 d`` FLOP a live pair and head forward, ``10 d`` backward; q and O
    at 32 heads, k and v at the 4 a grouped kernel could not avoid."""
    fwd = flops_sdar.flash_block_cost("fwd", 1, 16384, 32, 4, 128, 4)
    pairs = 8192 ** 2 + 8192 * 4
    assert fwd["flops"] == 32 * pairs * 4 * 128
    assert round(fwd["flops"] / 1e12, 2) == 1.10
    assert fwd["bytes"] == 16384 * (2 * 36 * 128 * 2 + 32 * 4)
    bwd = flops_sdar.flash_block_cost("bwd", 1, 16384, 32, 4, 128, 4)
    assert bwd["flops"] == 32 * pairs * 10 * 128 == 2.5 * fwd["flops"]
    assert bwd["bytes"] == 16384 * (4 * 36 * 128 * 2 + 32 * 4)
    # compute-bound by far: 5.6 ms against 0.4 at the chip's peaks
    least = flops.roofline_seconds(fwd["flops"], fwd["bytes"], 197e12, 819e9)
    assert least["bound"] == "compute" and round(least["seconds"], 4) == 0.0056
    # half a causal call's products over as many rows
    causal = flops.flash_gqa_cost("fwd", 1, 16384, 32, 4, 128)
    assert 0.49 < fwd["flops"] / causal["flops"] < 0.51

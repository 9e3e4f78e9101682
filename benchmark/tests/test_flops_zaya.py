"""``lib/flops_zaya.py`` against a hand count of one chip's share of ZAYA1-8B
(ISSUE 35's numbers) and against the program's own description."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops_zaya


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "zaya1-8b.json")) as f:
        return json.load(f)


def test_the_parts_of_a_layer_by_hand(config):
    # q 2048 x 1024, k and v 2048 x 256, the way up 1024 x 2048
    products = 2048 * (1024 + 256 + 256) + 1024 * 2048
    assert products == 5_242_880
    # 1,280 channels: two taps and a bias each; ten heads: two [128, 128]
    # matrices each and a bias a channel; two temperatures
    mix = 3 * 1280 + 10 * 2 * 128 * 128 + 1280 + 2
    assert flops_zaya.cca_params(config) == products + mix == 5_575_682
    # 2048 -> 256 with a bias, gain, norm, two 256 -> 256 with biases, 17 out
    assert flops_zaya.router_params(config) \
        == 2049 * 256 + 512 + 2 * 257 * 256 + 256 * 17 == 660_992
    assert flops_zaya.expert_params(config) == 3 * 2048 * 2048 == 12_582_912
    assert flops_zaya.layer_params(config, 8) \
        == 5_575_682 + 660_992 + 8 * 12_582_912 + 10 * 2048


def test_param_count_is_the_programs(config):
    from easydl_tpu.models.zaya import describe

    n_layers = len(config["layer_types"])
    total = flops_zaya.param_count(config)
    assert total == n_layers * flops_zaya.layer_params(config, 8) \
        + 32784 * 2048 + 2048
    assert total == describe(**config["kwargs"]).param_count
    assert n_layers != 6 or round(total / 1e5) == 7087  # 708.7M


def test_train_flops_per_token_by_hand_and_against_the_program(config):
    from easydl_tpu.models.zaya import describe

    seq, n_layers = 8192, len(config["layer_types"])
    outside = 5_575_682 + 660_992 + 10 * 2048
    pairs = seq * (seq + 1) // 2
    want = 6.0 * (32784 * 2048 + 2048 + n_layers * outside) \
        + 12.0 * n_layers * 1024 * pairs / seq
    assert flops_zaya.train_flops_per_token(config, seq, 0.0) \
        == pytest.approx(want)
    # a row a token and layer is one expert's 75.5 MFLOP
    one = flops_zaya.train_flops_per_token(config, seq, 1.0)
    assert one - want == pytest.approx(n_layers * 6.0 * 12_582_912)
    # the program's count takes the score matrices whole (the convention:
    # twice what the causal mask keeps) and the experts at 8 / 17 rows
    active = flops_zaya.train_flops_per_token(config, seq, 8 / 17)
    program = describe(**config["kwargs"]).train_flops_per_token(seq)
    whole_scores = 12.0 * n_layers * 1024 * (seq - pairs / seq)
    assert program == pytest.approx(active + whole_scores, rel=1e-6)
    if n_layers == 6:
        # what ``mfu`` leaves out in the cell at the seed's load
        assert round(want / 1e6) == 930 and round(active / 1e6) == 1143

"""``lib/flops_joyai.py`` against a hand count of one chip's share of
JoyAI-LLM-Flash (ISSUE 37's numbers) and against the program's own
description."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops_joyai


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "joyai-llm-flash.json")) as f:
        return json.load(f)


def test_the_parts_of_a_layer_by_hand(config):
    # W_qa 2048 x 1536, W_qb 1536 x (32 x 192), W_kva 2048 x 576,
    # W_kvb 512 x (32 x 256), W_o (32 x 128) x 2048, and the two gains
    maps = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert maps == 26_345_472                        # the issue's 26.35M
    assert flops_joyai.mla_params(config) == maps + 1536 + 512
    assert flops_joyai.expert_params(config) == 3 * 2048 * 768 == 4_718_592
    assert flops_joyai.ffn_params(config, "dense", 0) == 3 * 2048 * 7168 \
        == 44_040_192
    # the router at its published 256 outputs with the bias, a shared expert
    assert flops_joyai.ffn_params(config, "sparse", 16) \
        == 2049 * 256 + 17 * 4_718_592
    assert round(flops_joyai.layer_params(config, "sparse", 16) / 1e5) == 1071
    assert round(flops_joyai.layer_params(config, "dense", 0) / 1e5) == 704
    assert round(flops_joyai.module_params(config, 16) / 1e5) == 1155


def test_param_count_is_the_programs(config):
    from easydl_tpu.models.joyai import describe

    total = flops_joyai.param_count(config)
    assert total == 2 * 16160 * 2048 + 2048 \
        + flops_joyai.layer_params(config, "dense", 0) \
        + 4 * flops_joyai.layer_params(config, "sparse", 16) \
        + flops_joyai.module_params(config, 16)
    assert total == describe(**config["kwargs"]).param_count
    assert round(total / 1e5) == 6804                # 680.4M
    without = dict(config, kwargs=dict(config["kwargs"], mtp=False))
    assert flops_joyai.param_count(without) \
        == describe(**without["kwargs"]).param_count


def test_train_flops_per_token_by_hand_and_against_the_program(config):
    from easydl_tpu.models.joyai import describe

    seq = 8192
    pairs = seq * (seq + 1) // 2
    mla = 26_345_472 + 2048
    outside = mla + 2049 * 256 + 4_718_592 + 2 * 2048   # a sparse layer
    dense = mla + 44_040_192 + 2 * 2048
    module = outside + 2 * 2048 * 2048 + 3 * 2048
    want = 6.0 * (2 * 16160 * 2048 + dense + 4 * outside + module) \
        + 6 * 6.0 * 32 * (192 + 128) * pairs / seq
    assert flops_joyai.train_flops_per_token(config, seq, 0.0) \
        == pytest.approx(want)
    # a row a token and sparse layer is one expert's 28.3 MFLOP, in five
    one = flops_joyai.train_flops_per_token(config, seq, 1.0)
    assert one - want == pytest.approx(5 * 6.0 * 4_718_592)
    # the program's count takes the score matrices whole (the convention:
    # twice what the causal mask keeps), the experts at 0.5 rows a token,
    # and the final norm's gain and the biases as parameters of a product
    active = flops_joyai.train_flops_per_token(config, seq, 0.5)
    program = describe(**config["kwargs"]).train_flops_per_token(seq)
    whole_scores = 6 * 6.0 * 32 * (192 + 128) * (seq - pairs / seq)
    assert program == pytest.approx(active + whole_scores, rel=1e-5)
    # what ``mfu`` leaves out in the cell at the seed's load
    assert round(want / 1e6) == 3328 and round(active / 1e6) == 3399


def test_the_kernels_cost_by_hand():
    seq, pairs = 8192, 8192 * 8193 // 2
    fwd = flops_joyai.mla_flash_cost("fwd", 2, seq, 32, 192, 128)
    assert fwd["flops"] == 2 * 32 * 2.0 * pairs * (192 + 128)
    # q and k at 192 lanes a head (the shared key copied 32 times: what
    # lies in HBM), v and O at 128, bf16; one float32 lse a row and head
    assert fwd["bytes"] == 2 * 32 * seq * ((2 * 192 + 2 * 128) * 2 + 4)
    dq = flops_joyai.mla_flash_cost("dq", 2, seq, 32, 192, 128)
    assert dq["flops"] == 2 * 32 * 2.0 * pairs * (2 * 192 + 128)
    assert dq["bytes"] == 2 * 32 * seq * ((3 * 192 + 3 * 128) * 2 + 4)
    dkv = flops_joyai.mla_flash_cost("dkv", 2, seq, 32, 192, 128)
    assert dkv["flops"] == 2 * 32 * 2.0 * pairs * (2 * 192 + 2 * 128)
    assert dkv["bytes"] == 2 * 32 * seq * ((3 * 192 + 4 * 128) * 2 + 4)
    # equal sizes: the accepted count of lib/flops.py, O beside dO aside
    from lib import flops
    same = flops_joyai.mla_flash_cost("fwd", 1, 1024, 16, 64, 64)
    assert same["flops"] == flops.flash_causal_cost("fwd", 16, 1024, 64)[
        "flops"]

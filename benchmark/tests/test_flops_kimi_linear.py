"""``lib/flops_kimi_linear.py`` against a hand count of one chip's share of
Kimi-Linear-48B-A3B (ISSUE 64's numbers) and against the program's own
description."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops_kimi_linear as flops


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.json")) as f:
        return json.load(f)


def test_the_parts_of_a_layer_by_hand(config):
    # q, k, v, o 4 x 2304 x 4096; three convolutions 3 x 4096 x 4; the
    # decay's map 2304 x 128 + 128 x 4096, A_log 32, dt_bias 4096; beta 2304
    # x 32; the gate's map 2304 x 128 + 128 x 4096; the head norm's gain 128
    kda = 37_748_736 + 49_152 + 294_912 + 524_288 + 32 + 4_096 + 73_728 \
        + 294_912 + 524_288 + 128
    assert kda == 39_514_272 == flops.kda_params(config)   # the issue's
    # q 2304 x 6144, kv_a 2304 x 576, its norm 512, kv_b 512 x 8192, o
    mla = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    assert mla == 29_114_880 == flops.mla_params(config)
    assert flops.expert_params(config) == 3 * 2304 * 1024 == 7_077_888
    assert flops.ffn_products(config, "kda_dense", 0) == 3 * 2304 * 9216 \
        == 63_700_992
    # the router at its published 256 outputs, a shared expert, 8 held; the
    # selection bias beside them
    assert flops.ffn_products(config, "kda_sparse", 8) + 256 \
        == 590_080 + 9 * 7_077_888
    assert flops.layer_params(config, "kda_dense", 8) == 103_219_872
    assert flops.layer_params(config, "kda_sparse", 8) == 103_809_952
    assert flops.layer_params(config, "mla_sparse", 8) == 93_410_560


def test_param_count_is_the_issues_and_the_programs(config):
    from easydl_tpu.models.kimi_linear import describe

    total = flops.param_count(config)
    assert total == 2 * 20480 * 2304 + 2304 + 103_219_872 \
        + 3 * 103_809_952 + 93_410_560
    assert total == 602_434_432
    assert total == describe(**config["kwargs"]).param_count


def test_the_recurrences_cost_has_no_chunk_in_it(config):
    cost = flops.kda_cost(config)
    # three 128 x 128 products a token and head forward, twice that backward
    assert cost["flops"] == 32 * 294_912 == 9_437_184
    assert cost["flops"] / 32 == 3 * 6 * 128 * 128
    # forward: q, k, v, o in bf16, g and beta in float32; backward: the same
    # read with do, and dq, dk, dv (bf16), dg, dbeta (float32) written
    rows = 32 * 128
    forward = 4 * rows * 2 + rows * 4 + 32 * 4
    assert cost["bytes"] == forward + forward + (3 * rows * 2 + rows * 4
                                                 + 32 * 4)
    assert cost["layers"] == 4
    import inspect

    assert "chunk" not in inspect.signature(flops.kda_cost).parameters
    assert "chunk" not in inspect.getsource(flops.kda_cost).split('"""')[2]
    # bound by bytes on a v5e: 170 ps a token and layer against 48
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12


def test_train_flops_per_token_by_hand_and_against_the_program(config):
    from easydl_tpu.models.kimi_linear import describe

    seq = 16384
    pairs = seq * (seq + 1) // 2
    kda = 37_748_736 + 2 * (294_912 + 524_288) + 73_728
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    sparse = 2304 * 256 + 7_077_888                 # router, shared expert
    products = 20480 * 2304 + (kda + 63_700_992) + 3 * (kda + sparse) \
        + (mla + sparse)
    want = 6.0 * products + 6.0 * 32 * 320 * pairs / seq + 4 * 9_437_184
    assert flops.train_flops_per_token(config, seq, 0.0) \
        == pytest.approx(want, rel=1e-12)
    # the issue's 852 MFLOP of forward a token, at the seed's 0.25 rows
    at_seed = flops.train_flops_per_token(config, seq, 0.25)
    assert round(at_seed / 3 / 1e6) == 852
    assert at_seed - want == pytest.approx(6.0 * 4 * 0.25 * 7_077_888)
    # the latent layer's scores: a fifth of the forward
    assert round(2.0 * 32 * 320 * pairs / seq / 1e6) == 168
    # the program's own count takes every key a query could see and the
    # routed experts at the seed's rows: more, never less
    cfg = describe(**config["kwargs"])
    assert cfg.train_flops_per_token(seq) > at_seed
    # the scores' other half, and six a parameter of the norms, taps and
    # biases that are no matrix product
    more = cfg.train_flops_per_token(seq) - at_seed
    assert more == pytest.approx(6.0 * 32 * 320 * (seq - pairs / seq),
                                 rel=5e-3)

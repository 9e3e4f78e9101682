"""Hand counts for ``lib/flops_looplm.py`` at the published widths — the
benchmark's slice (8 layers x 4 passes) and the model as published — and
agreement with the program's own count (``TransformerConfig``)."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, flops_looplm


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


def test_a_layer_by_hand(config):
    assert flops_looplm.layer_params(config) == (
        4 * 2048 * 2048          # q, k, v, o: 16,777,216
        + 3 * 2048 * 5632        # gate, up, down: 34,603,008
        + 4 * 2048)              # four norms: 8,192
    assert flops_looplm.layer_params(config) == 51_388_416


def test_parameters_of_the_slice_and_of_the_published_model(config):
    assert flops_looplm.param_count(config) == (
        49152 * 2048             # embedding: 100,663,296
        + 8 * 51_388_416         # 411,107,328
        + 2048                   # final norm
        + 49152 * 2048           # untied head
        + 2048 + 1)              # the exit gate
    assert flops_looplm.param_count(config) == 612_438_017
    published = dict(config, layer_types=["full_attention"] * 48)
    assert round(flops_looplm.param_count(published) / 1e6) == 2668
    # passes cost no parameters
    assert flops_looplm.param_count(dict(config, total_ut_steps=1)) \
        == 612_438_017


def test_flops_a_token_by_the_looped_count(config):
    per_pass = (6 * (8 * 51_388_416 + 100_663_296)   # 3,070,623,744
                + 12 * 8 * 2048 * 4096)              # 805,306,368
    assert flops_looplm.train_flops_per_token(config, 4096) == 4 * per_pass
    assert flops_looplm.train_flops_per_token(config, 4096) \
        == 15_503_720_448
    # against gpt2-medium's 2.4 GFLOP a token: 6.4 times
    medium = flops.train_flops_per_token(354_871_296, 24, 1024, 1024)
    assert 6.3 < 15_503_720_448 / medium < 6.5
    # the heads' share: 15.6% here, about 3% at 48 layers
    heads = 4 * 6 * 100_663_296
    assert heads / 15_503_720_448 == pytest.approx(0.1558, abs=1e-3)
    published = dict(config, layer_types=["full_attention"] * 48)
    assert heads / flops_looplm.train_flops_per_token(published, 4096) \
        == pytest.approx(0.030, abs=2e-3)


def test_the_programs_own_count_agrees(config):
    from easydl_tpu.models.ouro import describe

    kwargs = {k: v for k, v in config["kwargs"].items()
              if k != "exit_entropy_weight"}
    cfg = describe(**kwargs)
    assert cfg.param_count == flops_looplm.param_count(config)
    # the program also counts the final norm's and the gate's 6 a parameter
    assert cfg.train_flops_per_token(4096) == \
        flops_looplm.train_flops_per_token(config, 4096) + 6 * (2048 + 2049)


def test_rope_and_norm_bytes_by_hand(config):
    # one call on [1, 4096, 2048] bf16: read and written once, two float32
    # [4096, 128] tables
    assert flops_looplm.rope_bytes(1, 4096, 2048, 128) \
        == 2 * 4096 * 2048 * 2 + 2 * 4096 * 128 * 4
    # a token: 32 layer applications x (q, k) x (forward, backward) x (read,
    # write) x 2048 bf16
    assert flops_looplm.rope_train_bytes_per_token(config) \
        == 32 * 2 * 2 * 2 * 2048 * 2
    assert flops_looplm.norm_train_bytes_per_token(config) \
        == 32 * 4 * 5 * 2048 * 2
    # at the chip's bandwidth a step's rotary (16,384 tokens, the
    # recomputed forward not counted) is 21 ms
    seconds = 16384 * flops_looplm.rope_train_bytes_per_token(config) / 819e9
    assert seconds == pytest.approx(0.021, rel=0.01)

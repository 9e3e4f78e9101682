"""``lib/flops_phi4flash.py`` against ISSUE 53's hand count of one chip's
share of Phi-4-mini-flash-reasoning, and against the program's real tree."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, flops_phi4flash
from lib.flops_laguna import seen_pairs


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _config("phi-4-mini-flash-reasoning")


def test_kinds_by_published_index(config):
    assert flops_phi4flash.kinds(config) == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]
    whole = dict(config, layer_ids=list(range(32)))
    kinds = flops_phi4flash.kinds(whole)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert config["published_counts"] == {
        "mamba1": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7,
        "kv_readers": 7, "memory_readers": 7}


def test_parameters_held(config):
    mixers = {kind: flops_phi4flash.mixer_params(config, kind)
              for kind in ("mamba", "window", "full", "gmu", "cross")}
    assert mixers == {"mamba": 41_123_840, "window": 19_660_800,
                      "full": 19_660_800, "gmu": 26_214_400,
                      "cross": 13_107_200}
    assert 3 * 2560 * 10240 == 78_643_200
    assert flops_phi4flash.matrix_params(config) == 2 * 41_123_840 \
        + 2 * 19_660_800 + 26_214_400 + 13_107_200 + 6 * 78_643_200 \
        + 25008 * 2560
    # ISSUE 53: 697.1M here, 12.55 GB at 18 bytes
    assert flops_phi4flash.param_count(config) == 697_094_272
    assert round(697_094_272 * 18 / 1e9, 2) == 12.55


@pytest.mark.parametrize("name", ["phi-4-mini-flash-reasoning",
                                  "phi4flash-test"])
def test_the_hand_count_is_the_real_trees(name):
    import jax

    from easydl_tpu.models.registry import get_model

    config = _config(name)
    bundle = get_model(config["factory"], **config["kwargs"])
    shapes = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
    assert flops.count_params(shapes) == flops_phi4flash.param_count(config)


def test_train_flops_a_token(config):
    seq = 16384
    pairs = 40 * 2 * 64 * 3  # a pair over 40 score heads: 64 deep, 128 wide
    assert flops_phi4flash.diff_pair_flops(config, 1, 1) == pairs
    whole = seen_pairs(seq) / seq          # 8,192.5 keys a token
    band = seen_pairs(seq, 512) / seq      # 504 and a little
    assert whole == 8192.5 and 503 < band < 512
    scan = 3.0 * 5120 * (7 * 16 + 3)
    want = 6.0 * flops_phi4flash.matrix_params(config) \
        + 3.0 * pairs * (2 * whole + band) + 2 * scan
    assert flops_phi4flash.train_flops_per_token(config, seq) \
        == pytest.approx(want)
    # ISSUE 53's reckoning: about 5 GFLOP a token forward and backward; the
    # two whole-sequence layers' pairs about 15% of it
    assert 4.5e9 < want < 5.1e9
    assert 0.12 < 3.0 * pairs * 2 * whole / want < 0.17


def test_the_scans_cost(config):
    cost = flops_phi4flash.selective_scan_cost(config)
    assert cost["layers"] == 2
    assert cost["flops"] == 3 * 5120 * 115 and cost["exp"] == 3 * 81_920
    # forward: x and y bf16, dt float32, B and C, a 128th of an entry state
    forward = 5120 * 8 + 64 + 5120 * 16 * 4 / 128
    backward = 5120 * 14 + 64 + 5120 * 16 * 4 / 128 + 2 * 16 * 4 * 10
    assert cost["bytes"] == forward + backward
    # bound by HBM on the published peaks: a hundredth of a millisecond a
    # thousand tokens and layer is what the share holds the kernels to
    least = flops.roofline_seconds(cost["flops"], cost["bytes"], 197e12,
                                   819e9)
    assert least["bound"] == "memory"


@pytest.mark.parametrize("kind,scores,values", [
    ("fwd", 1, 1), ("bwd", 3, 2), ("dq", 2, 1), ("dkv", 2, 2)])
def test_a_differential_call(config, kind, scores, values):
    seq = 16384
    cost = flops_phi4flash.flash_diff_cost(config, kind, 1, seq)
    assert cost["flops"] == seen_pairs(seq) * 40 * 2 * 64 * (
        scores + 2 * values)
    band = flops_phi4flash.flash_diff_cost(config, kind, 1, seq, 512)
    assert band["flops"] == seen_pairs(seq, 512) * 40 * 2 * 64 * (
        scores + 2 * values)
    assert band["bytes"] == cost["bytes"]
    if kind == "fwd":  # q, k and v at their own heads, O twice q's width
        assert cost["bytes"] == seq * ((2560 + 2560 + 5120) * 2 + 40 * 4)

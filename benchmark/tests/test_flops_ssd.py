"""Hand counts for ``lib/flops_ssd.py``, the hybrid's parameter count at the
benchmark's slice and as published, and agreement with the program's own
count (``ops/ssd.py``, ``TransformerConfig``)."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, flops_ssd


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_scan_forward_flops_by_hand():
    # chunk 256, one group of 128, 64 heads of 64
    assert flops_ssd.ssd_forward_flops_per_token(64, 64, 128, 1, 256) == (
        2 * 256 * 128          # C B^T: 65,536
        + 2 * 256 * 64 * 64    # (L o CB^T)(dt X): 2,097,152
        + 2 * 64 * 128 * 64    # the state the chunk leaves: 1,048,576
        + 2 * 64 * 128 * 64)   # the part it inherits: 1,048,576
    assert flops_ssd.ssd_forward_flops_per_token(64, 64, 128, 1, 256) \
        == 4_259_840


def test_scan_bytes_by_hand():
    got = flops_ssd.ssd_bytes_per_token(64, 64, 128, 1)
    # x and y 8,192 each in bf16, B and C 256 each, dt 256 in float32
    assert got["fwd"] == 8192 + 8192 + 256 + 256 + 256
    # x, dy, dx; B, C, dB, dC; dt, ddt
    assert got["bwd"] == 3 * 8192 + 4 * 256 + 2 * 256


def test_scan_roofline_bounds_are_close_at_these_shapes(config):
    cost = flops_ssd.ssd_train_cost_per_token(**flops_ssd.ssd_shape(config))
    assert cost["flops"] == 3 * 4_259_840
    least = flops.roofline_seconds(cost["flops"], cost["bytes"], 197e12,
                                   819e9)
    # 64.9 ns of MXU against 52.5 ns of HBM a token and layer: compute bound
    assert least["bound"] == "compute"
    assert least["seconds"] * 1e9 == pytest.approx(64.87, rel=1e-3)


@pytest.mark.parametrize("published,millions", [(False, 647.259328),
                                                (True, 3191.396096)])
def test_hybrid_parameter_count_by_hand(config, published, millions):
    layers = (["mamba"] * 5 + ["attention"]
              + (["mamba"] * 9 + ["attention"]) * 3 + ["mamba"] * 4)
    assert len(layers) == config["num_hidden_layers"] == 40
    assert layers[:6] == config["layer_types"]
    got = flops_ssd.hybrid_param_count(config, layers if published else ())
    assert got == round(millions * 1e6)


def test_hybrid_layers_by_hand(config):
    one = lambda kinds: (flops_ssd.hybrid_param_count(config, kinds)  # noqa: E731
                         - flops_ssd.hybrid_param_count(config, kinds[:-1]))
    # Mamba-2: 2048 x 8512 in, 5 x 4352 conv, 192 a head, 4096 norm,
    # 4096 x 2048 out; + 2 norms and the 3 x 2048 x 8192 MLP
    assert one(["mamba", "mamba"]) == 76_182_976
    assert one(["mamba", "attention"]) == 60_821_504
    assert config["vocab_size"] * config["hidden_size"] == 205_520_896


def test_hybrid_train_flops_are_the_issues_133_tflop_a_step(config):
    n = flops_ssd.hybrid_param_count(config)
    per_token = flops_ssd.hybrid_train_flops_per_token(n, config, 4096)
    assert per_token == 6.0 * n + 12.0 * 2048 * 4096 + 15.0 * 4_259_840
    assert per_token * 32768 / 1e12 == pytest.approx(132.65, rel=1e-3)


def test_the_program_counts_the_same(config):
    from easydl_tpu.models.granite_hybrid import describe
    from easydl_tpu.ops.ssd import ssd_flops_per_token

    cfg = describe(layer_types=config["layer_types"])
    assert cfg.param_count == flops_ssd.hybrid_param_count(config)
    assert ssd_flops_per_token(64, 64, 128, 1, 256) \
        == flops_ssd.ssd_forward_flops_per_token(64, 64, 128, 1, 256)
    assert cfg.train_flops_per_token(4096) \
        == flops_ssd.hybrid_train_flops_per_token(cfg.param_count, config,
                                                  4096)


def test_the_file_keeps_every_published_number(config):
    """Every number of the catalog's entry under its own key; only
    ``layer_types`` differs, and it is the first six published entries."""
    published = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "max_position_embeddings": 131072,
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 0, "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "vocab_size": 100352,
    }
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["layer_types"] == sorted(config["changed"])
    assert config["kwargs"]["layer_types"] == config["layer_types"]
    assert "over_weighted_by_the_cut" in config

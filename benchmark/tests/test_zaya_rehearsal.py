"""The steady driver rehearsed on the CPU with ZAYA1's test size (four
layers, 8 of 16 experts held, the skip choice) through ``run.py`` with its own
``BENCHMARK.zaya-test.json``, ``check_zaya`` deciding ``correct`` and the new
readers listed; ``BENCHMARK.json``'s new cell refusing to run without a chip;
and the configuration file holding every published number."""

import json
import os

import pytest

from conftest import BENCH, HERE
from listed import (check_nothing_to_read, check_rehearsal_file,
                    device_derived)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.zaya-test.json")
CELL = "zaya1-test.top1-8k-b2"
REAL_CELL = "zaya1-8b.top1-8k-b2"
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, {"compile_s", "compiles_in_window", "step_ms_p50", "step_spread_pct",
         "step_hbm_gib"}),
])
def test_zaya_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483653", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    assert "'state_rel_rms_layer_3'" in proc.stdout
    # the counters reach the check (on its seeded sequences) and
    # Trainer.train_step's metrics; the steady driver keeps a step's loss
    # alone, so no reader reports them from the window
    assert "'moe_dropped': 0.0" in proc.stdout
    assert "'chosen_not_top1_share': 0.0" in proc.stdout
    assert "'moe_skipped':" in proc.stdout
    assert "'router_state_rms':" in proc.stdout
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"mfu", "attn_time_pct", "cca_mix_time_pct", "flash_time_pct",
            "flash_fwd_roofline", "flash_bwd_roofline", "moe_time_pct",
            "experts_time_pct", "router_time_pct", "head_loss_time_pct",
            "device_idle_pct"} <= DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "top1-8k-b2"


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace, no counters."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["full_attention"]},
        {"readers": {"module": "cell_zaya"}, "layer_types": ["hybrid"],
         "kwargs": {"seq_len": 64}}))


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_the_file_holds_every_published_number():
    """Every key of the catalog row's ``config`` (copied here: the catalog
    is no file of the repository) is in the configuration file with its
    published value, save the three in ``reduced``; the deployment and the
    nine assumed sentences are written out."""
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "lm_head_bias": False, "max_position_embeddings": 131072,
        "model_type": "zaya", "moe_intermediate_size": 2048,
        "num_attention_heads": 8, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"}}
    with open(os.path.join(BENCH, "configs", "zaya1-8b.json")) as f:
        config = json.load(f)
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["changed"]) \
        == ["layer_types", "num_experts", "vocab_size"]
    for entry in config["changed"].values():
        assert set(entry) == {"source", "here", "why"}
    assert (config["num_experts"], config["num_experts_published"],
            config["router_width"]) == (8, 16, 17)
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (32784, 262272) and 32784 * 8 == 262272
    assert set(config["layer_types"]) == {"hybrid"}
    assert 4 <= len(config["layer_types"]) == len(
        config["kwargs"]["layer_types"])
    assert "expert-parallel 2 x data-parallel 4" in config["deployment"]
    letters = sorted(text[:3] for text in config["assumed"].values()
                     if text.startswith("("))
    assert letters == [f"({c})" for c in "abcdefghi"]
    assert config["kwargs"]["experts_held"] == [0, 8]
    assert config["source"].endswith("Zyphra/ZAYA1-8B/blob/main/config.json")

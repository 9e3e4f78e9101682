"""The steady driver rehearsed on the CPU with JoyAI-LLM's test size (a dense
layer, two sparse layers with 16 of 32 experts held, the module) through
``run.py`` with its own ``BENCHMARK.joyai-test.json``, ``check_joyai``
deciding ``correct`` and the new readers listed; ``BENCHMARK.json``'s new cell
refusing to run without a chip; and the configuration file holding every
published number."""

import json
import os

import pytest

from conftest import BENCH, HERE, ROOT
from listed import (check_nothing_to_read, check_rehearsal_file,
                    device_derived, reader as _reader)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.joyai-test.json")
CELL = "joyai-test.mla-mtp-8k-b2"
REAL_CELL = "joyai-llm-flash.mla-mtp-8k-b2"
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, {"compile_s", "compiles_in_window", "step_ms_p50", "step_spread_pct",
         "step_hbm_gib"}),
])
def test_joyai_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483653", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    assert "'state_rel_rms_mtp_layer'" in proc.stdout
    # the counters reach the check (on its seeded sequences) and
    # Trainer.train_step's metrics; the steady driver keeps a step's loss
    # alone, so no reader reports them from the window
    assert "'moe_dropped': 0.0" in proc.stdout
    assert "'chosen_not_top8_share': 0.0" in proc.stdout
    assert "'loss_main':" in proc.stdout and "'loss_mtp':" in proc.stdout
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"mfu", "attn_time_pct", "mla_proj_time_pct",
            "mla_key_rope_time_pct", "flash_time_pct", "flash_fwd_roofline",
            "flash_bwd_roofline", "mtp_time_pct", "head_loss_time_pct",
            "moe_time_pct", "experts_time_pct",
            "device_idle_pct"} <= DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "mla-mtp-8k-b2"
    with open(os.path.join(BENCH, "traffic", "mla-mtp-8k-b2.json")) as f:
        mix = json.load(f)
    assert (mix["global_batch"], mix["grad_accum"], mix["warmup_steps"],
            mix["trace_steps"]) == (2, 1, 2, 4)
    assert mix["optimizer"] == {"name": "adamw",
                                "args": {"learning_rate": 1e-06}}
    assert mix["tokens"]["support"] == 16160


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace, no counters."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["full_attention"]},
        {"readers": {"module": "cell_zaya"}, "layer_types": ["hybrid"]},
        {"readers": {"module": "cell_joyai"}, "layer_types": ["dense"],
         "kwargs": {"seq_len": 64}}))


def test_the_roofline_reader_on_a_synthetic_trace(monkeypatch):
    """One ``mla_fwd`` call named by the program, 10 ms a call on a v5e:
    the reader's share is the hand count's least time over it."""
    from lib import flops_joyai, scope_reduce

    with open(os.path.join(BENCH, "configs", "joyai-llm-flash.json")) as f:
        config = json.load(f)
    path = ("jit(train_step)/jvp(Transformer)/blocks_1/attention/"
            "multihead_attention/mla_fwd/pallas_call")
    monkeypatch.setattr(scope_reduce, "of_run", lambda artifacts: {
        "paths": {"mla_fwd.1": path, "rope.1": "jit(f)/rope/rope_fwd/x"},
        "whole_paths": True, "total_s": 1.0})
    artifacts = {
        "config": config, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "flash_calls": [
            {"name": "mla_fwd.1", "kernel": "mla_fwd", "kind": "fwd",
             "batch_heads": 2, "seq": 8192, "head_dim": 4096}],
        "trace_summary": {"ops": {"mla_fwd.1": {"calls": 4, "seconds": 0.04},
                                  "rope.1": {"calls": 4, "seconds": 0.01}}}}
    cost = flops_joyai.mla_flash_cost("fwd", 2, 8192, 32, 192, 128)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9  # compute-bound
    assert _reader("flash_fwd_roofline").read(artifacts) \
        == pytest.approx(100.0 * 4 * least / 0.04)
    assert 60 < 100.0 * least / 0.01 < 80      # 7 ms at the peak
    # no ``mla_bwd`` call in this program, no unrolled kernel in this cell
    assert _reader("flash_bwd_roofline").read(artifacts) is None
    assert _reader("flash_dq_roofline").read(artifacts) is None


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_the_file_holds_every_published_number():
    """Every key of the catalog row's ``config`` (copied here: the catalog
    is no file of the repository) is in the configuration file with its
    published value, save the two in ``reduced`` that it has; the deployment
    and the seven assumed sentences are written out."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    with open(os.path.join(BENCH, "configs", "joyai-llm-flash.json")) as f:
        config = json.load(f)
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["changed"]) \
        == ["layer_types", "n_routed_experts", "vocab_size"]
    for entry in config["changed"].values():
        assert set(entry) == {"source", "here", "why"}
    assert (config["n_routed_experts"],
            config["n_routed_experts_published"]) == (16, 256)
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (16160, 129280) and 16160 * 8 == 129280
    assert config["layer_types"] == ["dense"] + ["sparse"] * 4 \
        == config["kwargs"]["layer_types"]
    assert config["kwargs"]["mtp"] is True
    assert "expert-parallel 16" in config["deployment"]
    assert "sixteen" in config["deployment"]
    letters = sorted(text[:3] for text in config["assumed"].values()
                     if text.startswith("("))
    assert letters == [f"({c})" for c in "abcdefg"]
    assert config["kwargs"]["experts_held"] == [0, 16]
    assert config["source"].endswith(
        "jdopensource/JoyAI-LLM-Flash/blob/main/config.json")
    # the program's description reads the same widths
    from easydl_tpu.models.joyai import SIZES

    for key, value in SIZES["llm-flash"].items():
        if key != "n_routed_experts":
            assert config[key] == value, key
    assert SIZES["llm-flash"]["n_routed_experts"] == 256

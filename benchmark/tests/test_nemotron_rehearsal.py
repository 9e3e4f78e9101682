"""The steady driver rehearsed on the CPU with NemotronH's test size (MEM*EME:
two B/C groups, 4 heads over 2 key/value heads, 8 of 16 ungated experts held)
through ``run.py`` with its own ``BENCHMARK.nemotron-test.json``,
``check_nemotron_h`` deciding ``correct`` and the new readers listed;
``BENCHMARK.json``'s new cell refusing to run without a chip; and the
configuration file holding every published number."""

import importlib.util
import json
import os

import pytest

from conftest import BENCH, HERE, ROOT
from listed import (HOST_READERS, check_nothing_to_read,
                    check_rehearsal_file, device_derived, reader as _reader)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.nemotron-test.json")
CELL = "nemotron-test.ssm-moe-8k-b2"
REAL_CELL = "nemotron-3-nano-30b-a3b.ssm-moe-8k-b2"
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, HOST_READERS),
])
def test_nemotron_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483653", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    assert "'state_rel_rms_block_3'" in proc.stdout
    assert "'gated_norm_token_rel_max'" in proc.stdout
    # the counters reach the check (on its seeded sequences) and
    # Trainer.train_step's metrics; the steady driver keeps a step's loss
    # alone, so no reader reports them from the window
    assert "'moe_dropped': 0.0" in proc.stdout
    assert "'chosen_not_top6_share': 0.0" in proc.stdout
    # the logged-once lines say the groups and chunks, the form and the tiles
    assert "4 heads of 16 in 2 B/C groups" in proc.stderr
    assert "moe: relu2 experts (2 matrices each), 8 of 16 held" in proc.stderr
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"mfu", "ssm_time_pct", "moe_time_pct", "attn_time_pct",
            "head_loss_time_pct", "ssd_time_pct", "ssd_roofline",
            "conv1d_time_pct", "gated_norm_time_pct", "experts_time_pct",
            "shared_expert_time_pct", "route_time_pct", "flash_fwd_roofline",
            "flash_bwd_roofline", "flash_time_pct", "device_idle_pct",
            "fwd_time_pct", "bwd_time_pct", "remat_time_pct",
            "optimizer_time_pct", "unscoped_time_pct"} <= DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "ssm-moe-8k-b2"
    with open(os.path.join(BENCH, "traffic", "ssm-moe-8k-b2.json")) as f:
        mix = json.load(f)
    assert (mix["global_batch"], mix["grad_accum"], mix["warmup_steps"],
            mix["trace_steps"]) == (2, 1, 2, 4)
    assert mix["optimizer"] == {"name": "adamw",
                                "args": {"learning_rate": 1e-06}}
    assert mix["tokens"]["support"] == 16384 and mix["driver"] == "steady"


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace, no counters."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["full_attention"]},
        {"readers": {"module": "cell_joyai"}, "layer_types": ["dense"]},
        {"readers": {"module": "cell_nemotron_h"},
         "hybrid_override_pattern": "MEM*EME", "kwargs": {"seq_len": 64}}))


def _traced(monkeypatch, paths, seconds):
    """A traced run stood in: ``paths`` by instruction, ``seconds`` each."""
    from lib import scope_names, scope_reduce

    monkeypatch.setattr(scope_reduce, "of_run", lambda artifacts: {
        "paths": paths, "whole_paths": True,
        "total_s": sum(seconds.values())})
    monkeypatch.setattr(scope_reduce, "trace_file", lambda: __file__)
    monkeypatch.setattr(scope_names, "_self_seconds",
                        lambda path, mtime: seconds)
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "ssm-moe-8k-b2.json")) as f:
        traffic = json.load(f)
    return {"config": config, "traffic": traffic,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}}


def test_every_new_reader_returns_a_number_on_a_synthetic_trace(monkeypatch):
    """One operation under each of the program's names, a tenth of a second
    each: every time share reads its operations' part of the busy second,
    and both rooflines the hand count's least time over the time taken."""
    from lib import flops, flops_nemotron

    step = "jit(train_step)/jvp(Transformer)/"
    names = ["blocks_0/ssm/in_x/dot_general", "blocks_0/ssm/conv1d/mul",
             "blocks_0/ssm/ssd/while/body/dot_general",
             "blocks_0/ssm/gated_norm/rsqrt", "blocks_0/moe/router/dot",
             "blocks_0/moe/moe/dispatch/sort",
             "blocks_0/moe/moe/experts/grouped_rows/pallas_call",
             "blocks_0/moe/moe/combine/rows_to_tokens/pallas_call",
             "blocks_0/moe/moe/shared_expert/dot_general",
             "blocks_3/attention/multihead_attention/flash_fwd/pallas_call",
             "lm_head_loss/dot_general"]
    paths = {f"op.{i}": step + name for i, name in enumerate(names)}
    seconds = {op: 0.1 for op in paths}
    artifacts = _traced(monkeypatch, paths, seconds)
    total = 0.1 * len(names)
    artifacts.update(
        flash_calls=[{"name": "op.9", "kernel": "flash_fwd", "kind": "fwd",
                      "batch_heads": 2, "seq": 8192, "head_dim": 4096}],
        trace_summary={"busy_s": total, "ops": {
            "op.9": {"calls": 4, "seconds": 0.1}}},
        step_s=[0.5], steps=100, tokens_per_step=16384, window_s=50.0,
        chips=1)
    want = {"ssm_time_pct": 4, "moe_time_pct": 5, "attn_time_pct": 1,
            "head_loss_time_pct": 1, "ssd_time_pct": 1, "conv1d_time_pct": 1,
            "gated_norm_time_pct": 1, "experts_time_pct": 1,
            "shared_expert_time_pct": 1, "route_time_pct": 3,
            "router_time_pct": 1}
    for name, ops in want.items():
        assert _reader(name).read(artifacts) == pytest.approx(
            100.0 * 0.1 * ops / total), name
    cost = flops.flash_gqa_cost("fwd", 2, 8192, 32, 2, 128)
    assert _reader("flash_fwd_roofline").read(artifacts) \
        == pytest.approx(100.0 * 4 * cost["flops"] / 197e12 / 0.1)
    scan = flops_nemotron.ssd_train_cost_per_token(artifacts["config"])
    tokens = 4 * 2 * 8192 * 4  # steps, sequences, positions, M sub-layers
    least = max(tokens * scan["flops"] / 197e12,
                tokens * scan["bytes"] / 819e9)
    assert _reader("ssd_roofline").read(artifacts) \
        == pytest.approx(100.0 * least / 0.1)
    per_token = flops_nemotron.train_flops_per_token(artifacts["config"],
                                                     8192, 0.0)
    assert _reader("mfu").read(artifacts) == pytest.approx(
        100.0 * (100 * 16384 / 50.0) * per_token / 197e12)
    assert 0 < _reader("mfu").read(artifacts) < 100
    # the parent's program has no `gated_norm` scope: nothing to read there
    del paths["op.3"]
    assert _reader("gated_norm_time_pct").read(artifacts) is None


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_the_file_holds_every_published_number():
    """Every key of the catalog row's ``config`` (copied here: the catalog
    is no file of the repository) is in the configuration file with its
    published value, save the three in ``reduced``; the deployment and the
    seven assumed sentences are written out."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True}
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["changed"]) \
        == ["hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    for entry in config["changed"].values():
        assert set(entry) == {"source", "here", "why"}
    whole = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert config["hybrid_override_pattern_published"] == whole
    assert config["hybrid_override_pattern"] == whole[:9] == "MEMEM*EME" \
        == config["kwargs"]["hybrid_override_pattern"]
    assert (whole.count("M"), whole.count("E"), whole.count("*")) \
        == (23, 23, 6) and len(whole) == 52
    assert (config["n_routed_experts"],
            config["n_routed_experts_published"]) == (8, 128)
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (16384, 131072) and 16384 * 8 == 131072
    assert "expert-parallel 16" in config["deployment"]
    assert "sixteen" in config["deployment"]
    letters = sorted(text[:3] for text in config["assumed"].values()
                     if text.startswith("("))
    assert letters == [f"({c})" for c in "abcdefg"]
    assert config["kwargs"]["experts_held"] == [0, 8]
    assert config["source"].endswith(
        "nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    assert set(config["check"]["tolerances"]) >= {
        "router_logits_rel", "moe_dropped", "chosen_not_top6_share",
        "ssd_token_rel_max", "gated_norm_token_rel_max", "loss_abs",
        "grad_rel_rms_all", "grad_rel_rms_worst"}
    # the program's description reads the same widths
    from easydl_tpu.models.nemotron_h import SIZES

    for key, value in SIZES["nano-30b-a3b"].items():
        if key not in ("n_routed_experts", "hybrid_override_pattern"):
            assert config[key] == value, key
    assert SIZES["nano-30b-a3b"]["n_routed_experts"] == 128
    assert SIZES["nano-30b-a3b"]["hybrid_override_pattern"] == whole

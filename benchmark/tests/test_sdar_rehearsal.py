"""The steady driver rehearsed on the CPU with SDAR's test size (three layers
under block diffusion's mask: blocks of 4 in 64 tokens, 128 rows a sequence;
4 heads over 2 key/value heads with the q/k norm; 8 of 16 experts held under a
softmax top-4 router) through ``run.py`` with its own
``BENCHMARK.sdar-test.json``, ``check_sdar`` deciding ``correct`` and the new
readers listed; ``BENCHMARK.json``'s new cell refusing to run without a chip;
and the configuration file holding every published number."""

import json
import os

import pytest

from conftest import BENCH, HERE
from listed import (HOST_READERS, check_nothing_to_read,
                    check_rehearsal_file, device_derived, reader as _reader)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.sdar-test.json")
CELL = "sdar-test.blockdiff-8k-b1"
REAL_CELL = "sdar-30b-a3b-chat.blockdiff-8k-b1"
#: the program's own counters, as its loss reported them to the check
COUNTER_READERS = {"flash_live_pairs_pct", "masked_share_pct"}
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL) - COUNTER_READERS


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, HOST_READERS | COUNTER_READERS),
])
def test_sdar_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483659", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    assert "'state_rel_rms_layer_2'" in proc.stdout
    assert "'mask_position_rel_max'" in proc.stdout
    assert "'moe_dropped': 0.0" in proc.stdout
    assert "'masked_share_abs': 0.0" in proc.stdout
    assert "'diffusion_mean_t':" in proc.stdout
    # the logged-once lines say the objective's counters and the share
    assert "diffusion: blocks of 4 over 64 tokens, 128 rows a sequence; " \
        "flash_live_pairs 3 of flash_block_pairs 4" in proc.stderr
    assert "moe: swiglu experts (3 matrices each), 8 of 16 held" \
        in proc.stderr
    if trace:
        metrics = line["metrics"]
        assert metrics["compiles_in_window"]["value"] == 0
        assert metrics["flash_live_pairs_pct"]["value"] == 75.0
        assert 20 < metrics["masked_share_pct"]["value"] < 80


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"mfu", "attn_time_pct", "flash_time_pct", "flash_fwd_roofline",
            "flash_bwd_roofline", "moe_time_pct", "experts_time_pct",
            "route_time_pct", "router_time_pct", "rope_time_pct",
            "noise_time_pct", "device_idle_pct",
            "fwd_time_pct", "bwd_time_pct", "remat_time_pct",
            "head_loss_time_pct", "optimizer_time_pct",
            "unscoped_time_pct"} <= DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "blockdiff-8k-b1"
    mix = _json(BENCH, "traffic", "blockdiff-8k-b1.json")
    assert (mix["global_batch"], mix["grad_accum"], mix["warmup_steps"],
            mix["trace_steps"]) == (1, 1, 2, 4)
    assert mix["optimizer"] == {"name": "adamw",
                                "args": {"learning_rate": 1e-06}}
    assert mix["tokens"]["support"] == 18992 and mix["driver"] == "steady"
    bench = _json(os.path.dirname(BENCH), "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [REAL_CELL]]
    # (the per-head q/k norm's reader went with PR 57: the norm runs inside
    # the rotary kernel since PR 54, and `rope_time_pct` reads both)
    assert {m["name"] for m in new} == COUNTER_READERS | {"noise_time_pct"}
    # found by what they are, never by where they stand: the next PR
    # appends its own


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace, no counters."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["full_attention"]},
        {"readers": {"module": "cell_mellum"},
         "layer_types": ["sliding_attention"], "mlp_layer_types": ["sparse"],
         "kwargs": {"seq_len": 64}},
        {"readers": {"module": "cell_sdar"},
         "layer_types": ["full_attention"], "kwargs": {"seq_len": 64}}))
    counted = {"check": {"counters": {"moe_dropped": 0.0}}}
    for name in COUNTER_READERS:
        assert _reader(name).read(counted) is None
        assert _reader(name).read({}) is None


def test_every_new_reader_returns_a_number_on_a_synthetic_trace(monkeypatch):
    """One operation under each of the program's names, a tenth of a second
    each: every time share reads its operations' part of the busy second,
    the two rooflines the hand count's least time over the time taken, and
    the two counters what the check was told."""
    from lib import flops_sdar, scope_names, scope_reduce

    step = "jit(train_step)/jvp(Transformer)/"
    attend = "blocks/attention/multihead_attention/"
    names = ["noise/concatenate", "noise/jit(_uniform)/threefry2x32",
             attend + "jit(_fwd_call)/bd_fwd/pallas_call",
             attend + "bd_bwd/pallas_call",
             "blocks/attention/qk_rmsnorm/mul",
             "blocks/attention/q/dot_general",
             attend + "rope/rope_fwd/pallas_call",
             "blocks/moe/moe/router/dot",
             "blocks/moe/moe/experts/grouped_rows/pallas_call",
             "lm_head_loss/dot_general"]
    paths = {f"op.{i}": step + name for i, name in enumerate(names)}
    paths["op.3"] = paths["op.3"].replace("jvp(", "transpose(jvp(").replace(
        "r)/", "r))/")
    seconds = {op: 0.1 for op in paths}
    monkeypatch.setattr(scope_reduce, "of_run", lambda artifacts: {
        "paths": paths, "whole_paths": True,
        "total_s": sum(seconds.values())})
    monkeypatch.setattr(scope_reduce, "trace_file", lambda: __file__)
    monkeypatch.setattr(scope_names, "_self_seconds",
                        lambda path, mtime: seconds)
    config = _json(BENCH, "configs", "sdar-30b-a3b-chat.json")
    call = {"batch_heads": 1, "seq": 16384, "head_dim": 4096}
    artifacts = {
        "config": config,
        "traffic": _json(BENCH, "traffic", "blockdiff-8k-b1.json"),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "flash_calls": [dict(call, name="op.2", kind="fwd"),
                        dict(call, name="op.3", kind="bwd")],
        "trace_summary": {"busy_s": 1.0, "ops": {
            op: {"calls": 24, "seconds": 0.1} for op in ("op.2", "op.3")}},
        "check": {"counters": {"diffusion_masked_share": 0.4932,
                               "flash_live_pairs": 288.0,
                               "flash_block_pairs": 1024.0}},
        "step_s": [0.5], "steps": 100, "tokens_per_step": 8192,
        "window_s": 50.0, "chips": 1}
    want = {"noise_time_pct": 2, "rope_time_pct": 1,
            "flash_time_pct": 2, "moe_time_pct": 2, "router_time_pct": 1,
            "experts_time_pct": 1}
    for name, ops in want.items():
        assert _reader(name).read(artifacts) == pytest.approx(10.0 * ops), name
    for kind in ("fwd", "bwd"):
        cost = flops_sdar.flash_block_cost(kind, 1, 16384, 32, 4, 128, 4)
        assert _reader(f"flash_{kind}_roofline").read(artifacts) \
            == pytest.approx(100.0 * 24 * cost["flops"] / 197e12 / 0.1), kind
    assert _reader("flash_live_pairs_pct").read(artifacts) == 28.125
    assert _reader("masked_share_pct").read(artifacts) \
        == pytest.approx(49.32)
    per_token = flops_sdar.train_flops_per_token(config, 8192, 0.0)
    assert _reader("mfu").read(artifacts) == pytest.approx(
        100.0 * (100 * 8192 / 50.0) * per_token / 197e12)
    assert 0 < _reader("mfu").read(artifacts) < 100
    # a program whose calls carry other names: nothing to read
    paths["op.2"] = paths["op.2"].replace("bd_fwd", "flash_fwd")
    assert _reader("flash_fwd_roofline").read(artifacts) is None


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_the_file_holds_every_published_number():
    """Every key of the catalog row's ``config`` (copied here: the catalog
    is no file of the repository) is in the configuration file with its
    published value, save the two in ``reduced`` that it has; the cut, the
    deployment and the seven assumed sentences are written out."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    config = _json(BENCH, "configs", "sdar-30b-a3b-chat.json")
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["changed"]) \
        == ["layer_types", "num_experts", "vocab_size"]
    for entry in config["changed"].values():
        assert set(entry) == {"source", "here", "why"}
    assert config["layer_types"] == ["full_attention"] * 6 \
        == config["kwargs"]["layer_types"]
    assert (config["num_experts"], config["num_experts_published"],
            config["router_width"]) == (16, 128, 128)
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (18992, 151936) and 18992 * 8 == 151936
    assert config["block_length"] == config["kwargs"]["block_length"] == 4
    assert "expert-parallel 8" in config["deployment"]
    assert "eight" in config["deployment"]
    letters = sorted(text[:3] for text in config["assumed"].values()
                     if text.startswith("("))
    assert letters == [f"({c})" for c in "abcdefg"]
    assert config["kwargs"]["experts_held"] == [0, 16]
    assert config["source"].endswith(
        "JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    assert set(config["check"]["tolerances"]) >= {
        "rope_table_abs", "router_logits_abs", "moe_dropped",
        "chosen_not_top8_share", "mask_position_rel_max",
        "mask_edge_rel_max", "masked_share_abs", "loss_abs",
        "grad_rel_rms_all", "grad_rel_rms_worst"}
    # the program's description reads the same widths
    from easydl_tpu.models.sdar import SIZES

    for key, value in SIZES["30b-a3b-chat"].items():
        if key != "num_experts":
            assert config[key] == value, key
    assert SIZES["30b-a3b-chat"]["num_experts"] == 128

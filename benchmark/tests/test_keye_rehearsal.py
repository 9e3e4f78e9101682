"""The steady driver rehearsed on the CPU with Keye's test size (three layers
behind a learned index of 2 heads of 64, top-96 of 256 tokens; 4 heads over 2
key/value heads of 128 with the q/k norm; 8 of 16 experts held under a softmax
top-4 router) through ``run.py`` with its own ``BENCHMARK.keye-test.json``,
``check_keye`` deciding ``correct`` and the new readers listed;
``BENCHMARK.json``'s new cell refusing to run without a chip; and the
configuration file holding every published number."""

import json
import os

import pytest

from conftest import BENCH, HERE
from listed import (HOST_READERS, check_nothing_to_read,
                    check_rehearsal_file, device_derived, reader as _reader)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.keye-test.json")
CELL = "keye-test.dsa-16k-b1"
REAL_CELL = "keye-vl-2.0-30b-a3b.dsa-16k-b1"
#: the program's own counters, as its loss reported them to the check
COUNTER_READERS = {"index_selected_pct", "index_live_tiles_pct"}
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL) - COUNTER_READERS
NEW = COUNTER_READERS | {"index_time_pct", "index_topk_time_pct",
                         "index_scores_roofline", "index_bwd_roofline"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, HOST_READERS | COUNTER_READERS),
])
def test_keye_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483659", "--seconds", "5", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    for name in ("'state_rel_rms_layer_2'", "'select_position_rel_max'",
                 "'index_sets_differ_share'", "'index_loss_abs'",
                 "'moe_dropped': 0.0", "'index_chosen_not_topk_share': 0.0",
                 "'rope_table_abs': 0.0", "'index_score_rms':"):
        assert name in proc.stdout, name
    assert "indexed attention: XLA reference path under the selection " \
        "written out" in proc.stderr
    assert "index_selected 0.0 MB" in proc.stderr  # kept across remat
    assert "moe: swiglu experts (3 matrices each), 8 of 16 held" \
        in proc.stderr
    if trace:
        metrics = line["metrics"]
        assert metrics["compiles_in_window"]["value"] == 0
        assert metrics["index_live_tiles_pct"]["value"] == 100.0
        assert metrics["index_selected_pct"]["value"] == pytest.approx(
            100.0 * 20016 / 32896)


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"mfu", "attn_time_pct", "flash_time_pct", "flash_fwd_roofline",
            "flash_bwd_roofline", "moe_time_pct", "experts_time_pct",
            "route_time_pct", "router_time_pct", "rope_time_pct",
            "device_idle_pct", "fwd_time_pct", "bwd_time_pct",
            "remat_time_pct", "head_loss_time_pct", "optimizer_time_pct",
            "unscoped_time_pct", "index_time_pct", "index_topk_time_pct",
            "index_scores_roofline", "index_bwd_roofline"} <= DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "dsa-16k-b1"
    mix = _json(BENCH, "traffic", "dsa-16k-b1.json")
    assert (mix["global_batch"], mix["grad_accum"], mix["warmup_steps"],
            mix["trace_steps"]) == (1, 1, 2, 4)
    assert mix["optimizer"] == {"name": "adamw",
                                "args": {"learning_rate": 1e-06}}
    assert mix["tokens"]["support"] == 18992 and mix["driver"] == "steady"
    bench = _json(os.path.dirname(BENCH), "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [REAL_CELL]]
    assert {m["name"] for m in new} == NEW
    assert len(bench["per_layer"]) == 94
    assert len(bench["configs"]) == 12 and len(bench["workloads"]) == 14
    # found by what they are, never by where they stand: the next PR
    # appends its own


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace, no counters."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["full_attention"]},
        {"readers": {"module": "cell_sdar"},
         "layer_types": ["full_attention"], "kwargs": {"seq_len": 64}},
        {"readers": {"module": "cell_keye"},
         "layer_types": ["full_attention"], "kwargs": {"seq_len": 256}}))
    counted = {"check": {"counters": {"moe_dropped": 0.0}}}
    for name in NEW:
        assert _reader(name).read(counted) is None, name
        assert _reader(name).read({}) is None, name


def test_every_new_reader_returns_a_number_on_a_synthetic_trace(monkeypatch):
    """One operation under each of the program's names, a tenth of a second
    each: every time share reads its operations' part of the busy second,
    the four rooflines the hand count's least time over the time taken, and
    the two counters what the check was told."""
    from lib import flops_keye, scope_names, scope_reduce

    step = "jit(train_step)/jvp(Transformer)/"
    attend = "blocks/attention/"
    names = [attend + "index/index_q/dot_general",
             attend + "index/index_topk/jit(_select_call)/index_select/"
             "pallas_call",
             attend + "index_loss/jit(_kl_call)/index_kl/pallas_call",
             attend + "jit(_fwd_call)/dsa_fwd/pallas_call",
             attend + "dsa_bwd/pallas_call",
             attend + "index_loss/mul",
             attend + "rope/rope_norm_fwd/pallas_call",
             "blocks/moe/moe/router/dot",
             "blocks/moe/moe/experts/grouped_rows/pallas_call",
             "lm_head_loss/dot_general"]
    paths = {f"op.{i}": step + name for i, name in enumerate(names)}
    for op in ("op.4", "op.5"):
        paths[op] = paths[op].replace("jvp(", "transpose(jvp(").replace(
            "r)/", "r))/")
    seconds = {op: 0.1 for op in paths}
    monkeypatch.setattr(scope_reduce, "of_run", lambda artifacts: {
        "paths": paths, "whole_paths": True,
        "total_s": sum(seconds.values())})
    monkeypatch.setattr(scope_reduce, "trace_file", lambda: __file__)
    monkeypatch.setattr(scope_names, "_self_seconds",
                        lambda path, mtime: seconds)
    config = _json(BENCH, "configs", "keye-vl-2.0-30b-a3b.json")
    call = {"batch_heads": 1, "seq": 16384, "head_dim": 4096}
    artifacts = {
        "config": config,
        "traffic": _json(BENCH, "traffic", "dsa-16k-b1.json"),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "flash_calls": [dict(call, name="op.3", kind="fwd"),
                        dict(call, name="op.4", kind="bwd")],
        "trace_summary": {"busy_s": 1.0, "ops": {
            op: {"calls": 24, "seconds": 0.1} for op in ("op.3", "op.4")}},
        "check": {"counters": {"index_selected_pairs": 31458304.0,
                               "index_causal_pairs": 134225920.0,
                               "index_live_tiles": 49000.0,
                               "index_tiles": 49536.0}},
        "step_s": [1.0], "steps": 50, "tokens_per_step": 16384,
        "window_s": 50.0, "chips": 1}
    want = {"index_time_pct": 4, "index_topk_time_pct": 1, "rope_time_pct": 1,
            "flash_time_pct": 2, "moe_time_pct": 2, "router_time_pct": 1,
            "experts_time_pct": 1}
    for name, ops in want.items():
        assert _reader(name).read(artifacts) == pytest.approx(10.0 * ops), name
    for kind in ("fwd", "bwd"):
        cost = flops_keye.flash_selected_cost(kind, 1, 16384, 32, 4, 128,
                                              2048)
        assert _reader(f"flash_{kind}_roofline").read(artifacts) \
            == pytest.approx(100.0 * 24 * cost["flops"] / 197e12 / 0.1), kind
    # four traced steps of one sequence through six layers, once each
    for name, cost in (
            ("index_scores_roofline",
             flops_keye.index_select_cost(config, 16384)),
            ("index_bwd_roofline", flops_keye.index_loss_cost(config, 16384))):
        share = _reader(name).read(artifacts)
        assert share == pytest.approx(
            100.0 * 24 * cost["flops"] / 197e12 / 0.1), name
        assert 0 < share < 100, name
    assert _reader("index_selected_pct").read(artifacts) \
        == pytest.approx(23.4368, abs=1e-3)
    assert _reader("index_live_tiles_pct").read(artifacts) \
        == pytest.approx(100.0 * 49000 / 49536)
    per_token = flops_keye.train_flops_per_token(config, 16384, 0.0)
    assert _reader("mfu").read(artifacts) == pytest.approx(
        100.0 * (50 * 16384 / 50.0) * per_token / 197e12)
    assert 0 < _reader("mfu").read(artifacts) < 100
    # a program whose calls carry other names: nothing to read
    paths["op.1"] = paths["op.1"].replace("index_select", "scores")
    paths["op.3"] = paths["op.3"].replace("dsa_fwd", "flash_fwd")
    assert _reader("index_scores_roofline").read(artifacts) is None
    assert _reader("flash_fwd_roofline").read(artifacts) is None


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_the_file_holds_every_published_number():
    """Every key of the catalog row's ``config`` (copied here: the catalog
    is no file of the repository) is in the configuration file with its
    published value, save the three in ``reduced`` that it has; the cut, the
    deployment and the eight assumed sentences are written out."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    config = _json(BENCH, "configs", "keye-vl-2.0-30b-a3b.json")
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["changed"]) \
        == ["layer_types", "num_experts", "num_local_experts", "vocab_size"]
    for entry in config["changed"].values():
        assert set(entry) == {"source", "here", "why"}
    assert config["layer_types"] == ["full_attention"] * 6 \
        == config["kwargs"]["layer_types"]
    assert (config["num_experts"], config["num_local_experts"],
            config["num_experts_published"], config["router_width"]) \
        == (16, 16, 128, 128)
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (18992, 151936) and 18992 * 8 == 151936
    assert "expert-parallel 8" in config["deployment"]
    assert "eight" in config["deployment"]
    letters = sorted(text[:3] for text in config["assumed"].values()
                     if text.startswith("("))
    assert letters == [f"({c})" for c in "abcdefgh"]
    assert config["kwargs"]["experts_held"] == [0, 16]
    assert config["kwargs"]["seq_len"] == 16384
    assert config["source"].endswith(
        "Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert set(config["check"]["tolerances"]) >= {
        "rope_table_abs", "router_logits_abs", "moe_dropped",
        "chosen_not_top8_share", "index_chosen_not_topk_share",
        "index_sets_differ_share", "select_position_rel_max",
        "select_edge_rel_max", "loss_abs", "index_loss_abs",
        "grad_rel_rms_all", "grad_rel_rms_worst"}
    # the program's description reads the same widths
    from easydl_tpu.models.keye import SIZES

    mine = SIZES["vl-2.0-30b-a3b"]
    for key, value in mine.items():
        if key in config["sa_config"]:
            assert config["sa_config"][key] == value, key
        elif key != "num_experts":
            assert config[key] == value, key
    assert mine["num_experts"] == 128

"""The steady driver rehearsed on the CPU with Mellum 2's test size (three
window + sparse layers, full + sparse, window + sparse: a window of 24 in 64
positions, 4 heads over 2 key/value heads, 4 of 16 experts held under a softmax
top-4 router, nothing shared) through ``run.py`` with its own
``BENCHMARK.mellum-test.json``, ``check_mellum`` deciding ``correct`` and the
new readers listed; ``BENCHMARK.json``'s new cell refusing to run without a
chip; and the configuration file holding every published number."""

import importlib.util
import json
import os

import pytest

from conftest import BENCH, HERE, ROOT
from listed import (HOST_READERS, check_nothing_to_read,
                    check_rehearsal_file, device_derived, reader as _reader)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.mellum-test.json")
CELL = "mellum-test.top8-swa1k-8k-b2"
REAL_CELL = "mellum2-12b-a2.5b.top8-swa1k-8k-b2"
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, HOST_READERS),
])
def test_mellum_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483659", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    assert "'state_rel_rms_layer_4'" in proc.stdout
    assert "'window_position_rel_max'" in proc.stdout
    # the counters reach the check (on its seeded sequences) and
    # Trainer.train_step's metrics; the steady driver keeps a step's loss
    # alone, so no reader reports them from the window
    assert "'moe_dropped': 0.0" in proc.stdout
    assert "'chosen_not_top8_share': 0.0" in proc.stdout
    assert "'router_chosen_mass':" in proc.stdout
    # the logged-once line says the form, the share and the router
    assert "moe: swiglu experts (3 matrices each), 4 of 16 held" \
        in proc.stderr
    assert "router linear-softmax-renormalised, top-4" in proc.stderr
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"mfu", "moe_time_pct", "experts_time_pct", "router_time_pct",
            "band_attn_time_pct", "full_attn_time_pct", "band_flash_time_pct",
            "band_flash_fwd_roofline", "band_flash_dq_roofline",
            "band_flash_dkv_roofline", "flash_fwd_roofline",
            "flash_bwd_roofline", "flash_time_pct", "device_idle_pct",
            "fwd_time_pct", "bwd_time_pct", "remat_time_pct",
            "optimizer_time_pct", "unscoped_time_pct"} <= DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "top8-swa1k-8k-b2"
    mix = _json(BENCH, "traffic", "top8-swa1k-8k-b2.json")
    assert (mix["global_batch"], mix["grad_accum"], mix["warmup_steps"],
            mix["trace_steps"]) == (2, 1, 2, 4)
    assert mix["optimizer"] == {"name": "adamw",
                                "args": {"learning_rate": 1e-06}}
    assert mix["tokens"]["support"] == 24576 and mix["driver"] == "steady"


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace, no counters."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["full_attention"]},
        {"readers": {"module": "cell_laguna"}, "sliding_window": 512,
         "layer_types": ["sliding_attention"], "mlp_layer_types": ["sparse"]},
        {"readers": {"module": "cell_mellum"},
         "layer_types": ["sliding_attention"], "mlp_layer_types": ["sparse"],
         "kwargs": {"seq_len": 64}}))


def _traced(monkeypatch, paths, seconds):
    """A traced run stood in: ``paths`` by instruction, ``seconds`` each."""
    from lib import scope_names, scope_reduce

    monkeypatch.setattr(scope_reduce, "of_run", lambda artifacts: {
        "paths": paths, "whole_paths": True,
        "total_s": sum(seconds.values())})
    monkeypatch.setattr(scope_reduce, "trace_file", lambda: __file__)
    monkeypatch.setattr(scope_names, "_self_seconds",
                        lambda path, mtime: seconds)
    return {"config": _json(BENCH, "configs", "mellum2-12b-a2.5b.json"),
            "traffic": _json(BENCH, "traffic", "top8-swa1k-8k-b2.json"),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}}


def test_every_new_reader_returns_a_number_on_a_synthetic_trace(monkeypatch):
    """One operation under each of the program's names, a tenth of a second
    each: every time share reads its operations' part of the busy second,
    and the four rooflines the hand count's least time over the time
    taken."""
    from lib import flops, flops_mellum

    step = "jit(train_step)/jvp(Transformer)/"
    band = "blocks_0/attention/multihead_attention/"
    names = [band + "swa_fwd/pallas_call", band + "swa_bwd_dq/pallas_call",
             band + "swa_bwd_dkv/pallas_call",
             "blocks_0/attention/q/dot_general",
             "blocks_1/attention/multihead_attention/flash_fwd/pallas_call",
             "blocks_1/attention/multihead_attention/flash_bwd/pallas_call",
             "blocks_0/moe/moe/router/dot",
             "blocks_0/moe/moe/dispatch/sort",
             "blocks_1/moe/moe/experts/grouped_rows/pallas_call",
             "blocks_0/moe/moe/combine/rows_to_tokens/pallas_call",
             "lm_head/dot_general"]
    paths = {f"op.{i}": step + name for i, name in enumerate(names)}
    for op in ("op.1", "op.2", "op.5"):  # the backward's
        paths[op] = paths[op].replace("jvp(", "transpose(jvp(").replace(
            "r)/", "r))/")
    seconds = {op: 0.1 for op in paths}
    artifacts = _traced(monkeypatch, paths, seconds)
    call = {"batch_heads": 2, "seq": 8192, "head_dim": 4096}
    artifacts.update(
        flash_calls=[dict(call, name=f"op.{i}", kind=kind) for i, kind in
                     enumerate(("fwd", "dq", "dkv"))]
        + [dict(call, name="op.4", kind="fwd"),
           dict(call, name="op.5", kind="bwd")],
        trace_summary={"busy_s": 0.1 * len(names), "ops": {
            f"op.{i}": {"calls": 4, "seconds": 0.1}
            for i in (0, 1, 2, 4, 5)}},
        step_s=[0.3], steps=160, tokens_per_step=16384, window_s=50.0,
        chips=1)
    total = 0.1 * len(names)
    want = {"moe_time_pct": 4, "experts_time_pct": 1, "router_time_pct": 1,
            "route_time_pct": 3, "band_attn_time_pct": 4,
            "full_attn_time_pct": 2, "band_flash_time_pct": 3,
            "flash_time_pct": 5}
    for name, ops in want.items():
        assert _reader(name).read(artifacts) == pytest.approx(
            100.0 * 0.1 * ops / total), name
    for kind in ("fwd", "dq", "dkv"):
        cost = flops_mellum.flash_band_cost(kind, 2, 8192, 4096, 128, 1024)
        assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
        assert _reader(f"band_flash_{kind}_roofline").read(artifacts) \
            == pytest.approx(100.0 * 4 * cost["flops"] / 197e12 / 0.1), kind
    cost = flops.flash_gqa_cost("fwd", 2, 8192, 32, 4, 128)
    assert _reader("flash_fwd_roofline").read(artifacts) \
        == pytest.approx(100.0 * 4 * cost["flops"] / 197e12 / 0.1)
    back = flops.flash_gqa_cost("bwd", 2, 8192, 32, 4, 128)
    assert back["flops"] == 2.5 * cost["flops"]  # five products for two
    assert _reader("flash_bwd_roofline").read(artifacts) \
        == pytest.approx(100.0 * 4 * back["flops"] / 197e12 / 0.1)
    per_token = flops_mellum.train_flops_per_token(artifacts["config"],
                                                   8192, 0.0)
    assert _reader("mfu").read(artifacts) == pytest.approx(
        100.0 * (160 * 16384 / 50.0) * per_token / 197e12)
    assert 0 < _reader("mfu").read(artifacts) < 100
    # a program without the band kernels (the looped path): nothing to read
    for op in ("op.0", "op.1", "op.2"):
        del paths[op]
    assert _reader("band_flash_time_pct").read(artifacts) is None
    assert _reader("band_flash_fwd_roofline").read(artifacts) is None


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_the_file_holds_every_published_number():
    """Every key of the catalog row's ``config`` (copied here: the catalog
    is no file of the repository) is in the configuration file with its
    published value, save the four in ``reduced``; the deployment and the
    six assumed sentences are written out."""
    period = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "use_sliding_window": True}
    config = _json(BENCH, "configs", "mellum2-12b-a2.5b.json")
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["changed"]) \
        == ["layer_types", "mlp_layer_types", "num_experts", "vocab_size"]
    for entry in config["changed"].values():
        assert set(entry) == {"source", "here", "why"}
    assert config["layer_types"] == period \
        == config["kwargs"]["layer_types"]
    assert config["mlp_layer_types"] == ["sparse"] * 4
    assert (config["num_experts"], config["num_experts_published"],
            config["router_width"]) == (16, 64, 64)
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (24576, 98304) and 24576 * 4 == 98304
    assert "expert-parallel 4" in config["deployment"]
    assert "four" in config["deployment"]
    letters = sorted(text[:3] for text in config["assumed"].values()
                     if text.startswith("("))
    assert letters == [f"({c})" for c in "abcdef"]
    assert config["kwargs"]["experts_held"] == [0, 16]
    assert config["source"].endswith(
        "JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    assert set(config["check"]["tolerances"]) >= {
        "rope_table_abs", "router_logits_abs", "moe_dropped",
        "chosen_not_top8_share", "window_position_rel_max", "loss_abs",
        "grad_rel_rms_all", "grad_rel_rms_worst"}
    # the program's description reads the same widths
    from easydl_tpu.models.mellum import SIZES

    size = SIZES["2-12b-a2.5b"]
    for key, value in size.items():
        if key not in ("num_experts", "layer_types", "mlp_layer_types",
                       "rope_parameters"):
            assert config[key] == value, key
    assert size["num_experts"] == 64
    assert list(size["layer_types"]) == period * 7
    assert list(size["mlp_layer_types"]) == ["sparse"] * 28
    for kind, scheme in size["rope_parameters"].items():
        assert scheme == {k: (float(v) if k not in (
            "rope_type", "original_max_position_embeddings") else v)
            for k, v in published["rope_parameters"][kind].items()}, kind

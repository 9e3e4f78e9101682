"""The steady driver rehearsed on the CPU with Phi-4-mini-flash's test size
(the six kinds of layer at a sixteenth of the widths: two Mamba-1 layers,
differential attention under a window of 8 and whole, a gated memory unit, a
cross layer) through ``run.py`` with its own ``BENCHMARK.phi4flash-test.json``,
``check_phi4flash`` deciding ``correct`` and the new readers listed;
``BENCHMARK.json``'s new cell refusing to run without a chip; and the
configuration file holding every published number."""

import json
import os

import pytest

from conftest import BENCH, HERE
from listed import (HOST_READERS, check_nothing_to_read,
                    check_rehearsal_file, device_derived, reader as _reader)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.phi4flash-test.json")
CELL = "phi4flash-test.sambay-16k-b1"
REAL_CELL = "phi-4-mini-flash-reasoning.sambay-16k-b1"
NEW = {"selective_scan_time_pct", "selective_scan_roofline", "gmu_time_pct",
       "diff_combine_time_pct", "cross_attn_time_pct"}
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, HOST_READERS),
])
def test_phi4flash_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483659", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    for name in ("state_rel_rms_layer_5", "scan_token_rel_max",
                 "diff_out_token_rel_max", "grad_rel_rms_worst"):
        assert f"'{name}'" in proc.stdout
    assert "'memory_abs': 0.0" in proc.stdout
    assert "'cross_kv_abs': 0.0" in proc.stdout
    assert "'kv_readers': 1.0, 'memory_readers': 1.0" in proc.stdout
    # the logged-once line says which scan ran and why
    assert "selective_scan: chunked scan in jax.numpy, not the kernels " \
        "(no tpu)" in proc.stderr
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    assert NEW | {"mfu", "attn_time_pct", "ffn_time_pct", "ssm_time_pct",
                  "conv1d_time_pct", "flash_time_pct", "flash_fwd_roofline",
                  "flash_bwd_roofline", "band_attn_time_pct",
                  "full_attn_time_pct", "band_flash_time_pct",
                  "band_flash_fwd_roofline", "band_flash_dq_roofline",
                  "band_flash_dkv_roofline", "head_loss_time_pct"} \
        <= DEVICE_DERIVED
    assert not {"ssd_time_pct", "ssd_roofline"} & DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "sambay-16k-b1"
    assert len(cell["why"]) <= 200
    mix = _json(BENCH, "traffic", "sambay-16k-b1.json")
    assert (mix["global_batch"], mix["grad_accum"], mix["warmup_steps"],
            mix["trace_steps"], mix["dispatch_ahead_steps"]) \
        == (1, 1, 2, 4, 9)
    assert "weights_seed" not in mix
    assert mix["optimizer"] == {"name": "adamw",
                                "args": {"learning_rate": 1e-06}}
    assert mix["tokens"]["support"] == 25008 and mix["driver"] == "steady"
    bench = _json(os.path.dirname(BENCH), "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [REAL_CELL]]
    assert {m["name"] for m in new} == NEW
    assert all(m["source"] == "device_trace" and m["unit"] == "%"
               for m in new)


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["full_attention"]},
        {"readers": {"module": "cell_granite_hybrid"},
         "layer_types": ["mamba", "attention"], "kwargs": {"seq_len": 64}},
        _json(BENCH, "configs", "phi4flash-test.json")))
    for name in NEW:
        assert _reader(name).read({}) is None


def test_every_new_reader_returns_a_number_on_a_synthetic_trace(monkeypatch):
    """One operation under each of the program's names, a tenth of a second
    each: every time share reads its operations' part of the busy second and
    the rooflines the hand count's least time over the time taken."""
    from lib import flops_phi4flash, scope_names, scope_reduce

    fwd = "jit(train_step)/jvp(Transformer)/"
    bwd = "jit(train_step)/transpose(jvp(Transformer))/"
    attend = "attention/multihead_attention/"
    scan = "blocks_0/ssm/selective_scan/"
    names = [
        fwd + scan + "jit(_fwd)/sscan_fwd/pallas_call",
        bwd + scan + "jit(_bwd)/sscan_bwd/pallas_call",
        fwd + scan + "transpose",
        fwd + "blocks_0/ssm/conv1d/jit(_conv_fwd)/conv1d_fwd/pallas_call",
        fwd + "blocks_4/ssm/gmu/in_gate/dot_general",
        fwd + "blocks_1/" + attend + "swa_fwd/pallas_call",
        bwd + "blocks_1/" + attend + "swa_bwd_dq/pallas_call",
        bwd + "blocks_1/" + attend + "swa_bwd_dkv/pallas_call",
        fwd + "blocks_3/" + attend + "jit(_fwd_call)/diff_fwd/pallas_call",
        bwd + "blocks_5/attention/cross/multihead_attention/"
              "jit(_bwd_call)/diff_bwd/pallas_call",
        fwd + "blocks_5/attention/cross/diff_combine/mul",
        fwd + "blocks_3/attention/diff_combine/mul",
        fwd + "blocks_2/ffn/up/dot_general",
        fwd + "lm_head_loss/dot_general"]
    paths = {f"op.{i}": name for i, name in enumerate(names)}
    seconds = {op: 0.1 for op in paths}
    monkeypatch.setattr(scope_reduce, "of_run", lambda artifacts: {
        "paths": paths, "whole_paths": True,
        "total_s": sum(seconds.values())})
    monkeypatch.setattr(scope_reduce, "trace_file", lambda: __file__)
    monkeypatch.setattr(scope_names, "_self_seconds",
                        lambda path, mtime: seconds)
    config = _json(BENCH, "configs", "phi-4-mini-flash-reasoning.json")
    traffic = _json(BENCH, "traffic", "sambay-16k-b1.json")
    call = {"batch_heads": 1, "seq": 16384}
    kinds = {"op.5": "fwd", "op.6": "dq", "op.7": "dkv", "op.8": "fwd",
             "op.9": "bwd"}
    artifacts = {
        "config": config, "traffic": traffic,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "flash_calls": [dict(call, name=op, kind=kind, head_dim=2560)
                        for op, kind in kinds.items()],
        "trace_summary": {"busy_s": 1.4, "ops": {
            op: {"calls": 4, "seconds": 0.1} for op in kinds}},
        "step_s": [0.85], "steps": 58, "tokens_per_step": 16384,
        "window_s": 50.0, "chips": 1}
    total = 10.0 * len(names) / 1.4
    want = {"selective_scan_time_pct": 3, "conv1d_time_pct": 1,
            "gmu_time_pct": 1, "ssm_time_pct": 5, "diff_combine_time_pct": 2,
            "cross_attn_time_pct": 2, "band_flash_time_pct": 3,
            "band_attn_time_pct": 3, "full_attn_time_pct": 4}
    for name, ops in want.items():
        assert _reader(name).read(artifacts) \
            == pytest.approx(total * ops / len(names)), name
    assert _reader("flash_time_pct").read(artifacts) \
        == pytest.approx(100 * 0.5 / 1.4)
    for name, kind, window in (
            ("flash_fwd_roofline", "fwd", 0), ("flash_bwd_roofline", "bwd", 0),
            ("band_flash_fwd_roofline", "fwd", 512),
            ("band_flash_dq_roofline", "dq", 512),
            ("band_flash_dkv_roofline", "dkv", 512)):
        cost = flops_phi4flash.flash_diff_cost(config, kind, 1, 16384, window)
        least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
        assert _reader(name).read(artifacts) \
            == pytest.approx(100.0 * 4 * least / 0.1), name
        assert 0 < _reader(name).read(artifacts) < 100
    cost = flops_phi4flash.selective_scan_cost(config)
    tokens = 4 * 16384 * 2
    assert _reader("selective_scan_roofline").read(artifacts) \
        == pytest.approx(100.0 * tokens * cost["bytes"] / 819e9 / 0.2)
    per_token = flops_phi4flash.train_flops_per_token(config, 16384)
    assert _reader("mfu").read(artifacts) == pytest.approx(
        100.0 * (58 * 16384 / 50.0) * per_token / 197e12)
    assert 0 < _reader("mfu").read(artifacts) < 100
    # a program whose calls carry other names: nothing to read
    paths["op.8"] = paths["op.8"].replace("diff_fwd", "flash_fwd")
    assert _reader("flash_fwd_roofline").read(artifacts) is None
    paths["op.0"] = paths["op.0"].replace("sscan_fwd", "ssd_fwd")
    paths["op.1"] = paths["op.1"].replace("sscan_bwd", "ssd_bwd")
    assert _reader("selective_scan_roofline").read(artifacts) is None


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_the_file_holds_every_published_number():
    """Every key of the catalog row's ``config`` (copied here: the catalog
    is no file of the repository) is in the configuration file with its
    published value, save the one in ``reduced`` that it has; the cut, the
    deployment and the eight assumed sentences are written out."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False}
    config = _json(BENCH, "configs", "phi-4-mini-flash-reasoning.json")
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["changed"]) \
        == ["layer_ids", "vocab_size"]
    for entry in config["changed"].values():
        assert set(entry) == {"source", "here", "why"}
    assert config["layer_ids"] == [0, 1, 16, 17, 18, 19] \
        == config["kwargs"]["layer_ids"]
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (25008, 200064) and 25008 * 8 == 200064
    assert config["kwargs"]["vocab"] == 25008
    assert "eight" in config["deployment"] and "8 slices" in \
        config["deployment"]
    letters = sorted(text[:3] for text in config["assumed"].values()
                     if text.startswith("("))
    assert letters == [f"({c})" for c in "abcdefgh"]
    for key in "abcdef":
        text, = (t for t in config["assumed"].values()
                 if t.startswith(f"({key})"))
        assert "not checked against modeling_phi4flash.py" in text
    assert config["over_weighted_by_the_cut"].startswith("published ratio")
    assert config["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert set(config["check"]["tolerances"]) >= {
        "loss_abs", "state_rel_rms_final", "token_rel_max",
        "logits_token_rel_max", "scan_token_rel_max", "memory_abs",
        "cross_kv_abs",
        "diff_before_norm_token_rel_max", "diff_out_rel_rms",
        "grad_rel_rms_all", "grad_rel_rms_worst"}
    # reported, not limited: no wrong program moves it (the file's `why`)
    assert "scan_operands_token_rel_max" not in config["check"]["tolerances"]
    # the program's description reads the same widths
    from easydl_tpu.models.phi4flash import SIZES

    for key, value in SIZES["mini-flash-reasoning"].items():
        if key != "channel_view":
            assert config[key] == value, key

"""The steady driver rehearsed on the CPU with Kimi Linear's test size (five
layers: a dense KDA layer, two sparse ones, a sparse latent layer without
positions, a sparse KDA layer; 8 of 32 experts held under a sigmoid top-4
router with a selection bias) through ``run.py`` with its own
``BENCHMARK.kimi-linear-test.json``, ``check_kimi_linear`` deciding
``correct`` and the two new readers listed; ``BENCHMARK.json``'s new cell
refusing to run without a chip; the kernels' calls kept out of
``lib/hlo.flash_calls``; and the configuration file holding every published
number."""

import json
import os

import pytest

from conftest import BENCH, HERE
from listed import (HOST_READERS, check_nothing_to_read,
                    check_rehearsal_file, device_derived, reader as _reader)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.kimi-linear-test.json")
CELL = "kimi-linear-test.kda-16k-b1"
REAL_CELL = "kimi-linear-48b-a3b.kda-16k-b1"
DEVICE_DERIVED = device_derived(REAL_CELL)
NEW = {"kda_time_pct", "kda_roofline"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, HOST_READERS),
])
def test_kimi_linear_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483659", "--seconds", "5", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    for name in ("'state_rel_rms_layer_4'", "'kda_inputs_token_rel_max'",
                 "'kda_out_token_rel_max'", "'kda_state_rel_rms'",
                 "'mla_latent_token_rel_max'", "'mla_attn_token_rel_max'",
                 "'moe_dropped': 0.0", "'chosen_not_top8_share': 0.0",
                 "'router_logits_rel': 0.0", "'kda_chunks': 1.0",
                 "'kda_decay_mean':", "'kda_beta_mean':", "'kda_state_rms':"):
        assert name in proc.stdout, name
    assert "kda: chunks in jax.numpy, not the kernels (no tpu), 1 chunks " \
        "of 64 a sequence, 4 heads of 16 / 16" in proc.stderr
    assert "a (kda, moe) layer" in proc.stderr
    assert "a (latent_attention, moe) layer" in proc.stderr
    assert "moe: swiglu experts (3 matrices each), 8 of 32 held" \
        in proc.stderr
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"mfu", "attn_time_pct", "flash_time_pct", "flash_fwd_roofline",
            "flash_bwd_roofline", "moe_time_pct", "experts_time_pct",
            "route_time_pct", "router_time_pct", "shared_expert_time_pct",
            "ssm_time_pct", "conv1d_time_pct", "gated_norm_time_pct",
            "mla_proj_time_pct", "ffn_time_pct", "device_idle_pct",
            "fwd_time_pct", "bwd_time_pct", "remat_time_pct",
            "head_loss_time_pct", "optimizer_time_pct", "unscoped_time_pct",
            "kda_time_pct", "kda_roofline"} == DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "kda-16k-b1"
    mix = _json(BENCH, "traffic", "kda-16k-b1.json")
    assert (mix["global_batch"], mix["grad_accum"], mix["warmup_steps"],
            mix["trace_steps"]) == (1, 1, 2, 4)
    assert mix["optimizer"] == {"name": "adamw",
                                "args": {"learning_rate": 1e-06}}
    assert mix["tokens"]["support"] == 20480 and mix["driver"] == "steady"
    # the held experts' load followed the seed: the trained state's is fixed
    assert mix["weights_seed"] == 6400000201 and "0.53%" in mix["steadied"]
    assert mix["dispatch_ahead_steps"] == 12
    bench = _json(os.path.dirname(BENCH), "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [REAL_CELL]]
    assert {m["name"] for m in new} == NEW
    assert {m["better"] for m in new} == {"lower", "higher"}
    assert all(m["layer"] == "ops" and m["source"] == "device_trace"
               for m in new)
    # found by what they are, never by where they stand or how many there
    # are: the next PR appends its own
    for name in ("rope_time_pct", "mtp_time_pct", "ssd_roofline",
                 "ssd_time_pct", "mla_key_rope_time_pct"):
        entry, = (m for m in bench["per_layer"] if m["name"] == name)
        assert REAL_CELL not in entry["workloads"], name


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace, no module that
    states the recurrence's cost."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["kda_dense"]},
        {"readers": {"module": "cell_joyai"},
         "layer_types": ["dense"], "kwargs": {"seq_len": 64}},
        {"readers": {"module": "cell_kimi_linear"},
         "layer_types": ["kda_dense"], "kwargs": {"seq_len": 64}}))
    for name in NEW:
        assert _reader(name).read({"check": {"counters": {}}}) is None, name
        assert _reader(name).read({}) is None, name


def test_every_new_reader_returns_a_number_on_a_synthetic_trace(monkeypatch):
    """One operation under each of the program's names, a tenth of a second
    each: every time share reads its operations' part of the busy second,
    ``kda_roofline`` the hand count's least time over the time under
    ``kda``, well under 100."""
    from lib import flops_joyai, flops_kimi_linear, scope_names, scope_reduce

    step = "jit(train_step)/jvp(Transformer)/"
    mixer = "blocks_1/ssm/"
    names = [mixer + "kda/jit(_fwd)/kda_fwd/pallas_call",
             mixer + "kda/jit(_bwd)/kda_bwd/pallas_call",
             mixer + "kda/transpose",
             mixer + "kda_gates/softplus",
             mixer + "conv1d/jit(_conv_fwd)/conv1d_fwd/pallas_call",
             mixer + "gated_norm/mul",
             "blocks_2/attention/jit(_fwd_call)/mla_fwd/pallas_call",
             "blocks_2/attention/mla_up/dot_general",
             "blocks_2/moe/moe/shared_expert/dot_general",
             "lm_head_loss/dot_general"]
    paths = {f"op.{i}": step + name for i, name in enumerate(names)}
    paths["op.1"] = paths["op.1"].replace("jvp(", "transpose(jvp(").replace(
        "r)/", "r))/")
    seconds = {op: 0.1 for op in paths}
    monkeypatch.setattr(scope_reduce, "of_run", lambda artifacts: {
        "paths": paths, "whole_paths": True,
        "total_s": sum(seconds.values())})
    monkeypatch.setattr(scope_reduce, "trace_file", lambda: __file__)
    monkeypatch.setattr(scope_names, "_self_seconds",
                        lambda path, mtime: seconds)
    config = _json(BENCH, "configs", "kimi-linear-48b-a3b.json")
    artifacts = {
        "config": config,
        "traffic": _json(BENCH, "traffic", "kda-16k-b1.json"),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "flash_calls": [{"batch_heads": 1, "seq": 16384, "head_dim": 4096,
                         "name": "op.6", "kind": "fwd"}],
        "trace_summary": {"busy_s": 1.0, "ops": {
            "op.6": {"calls": 4, "seconds": 0.1}}},
        "step_s": [1.0], "steps": 50, "tokens_per_step": 16384,
        "window_s": 50.0, "chips": 1}
    want = {"kda_time_pct": 3, "ssm_time_pct": 6, "conv1d_time_pct": 1,
            "gated_norm_time_pct": 1, "flash_time_pct": 1,
            "attn_time_pct": 2, "mla_proj_time_pct": 1, "moe_time_pct": 1,
            "shared_expert_time_pct": 1, "head_loss_time_pct": 1}
    for name, ops in want.items():
        assert _reader(name).read(artifacts) == pytest.approx(10.0 * ops), name
    # four traced steps of one sequence through four KDA layers: bound by
    # the bytes (139,648 a token and layer at 819 GB/s)
    cost = flops_kimi_linear.kda_cost(config)
    tokens = 4 * 16384 * 4
    share = _reader("kda_roofline").read(artifacts)
    assert share == pytest.approx(
        100.0 * tokens * cost["bytes"] / 819e9 / 0.3)
    assert 0 < share < 100
    flash = flops_joyai.mla_flash_cost("fwd", 1, 16384, 32, 192, 128)
    assert _reader("flash_fwd_roofline").read(artifacts) == pytest.approx(
        100.0 * 4 * flash["flops"] / 197e12 / 0.1)
    per_token = flops_kimi_linear.train_flops_per_token(config, 16384, 0.0)
    assert _reader("mfu").read(artifacts) == pytest.approx(
        100.0 * (50 * 16384 / 50.0) * per_token / 197e12)
    assert 0 < _reader("mfu").read(artifacts) < 100
    # a program whose scope carries another name: nothing to read
    for op in ("op.0", "op.1", "op.2"):
        paths[op] = paths[op].replace("/kda/", "/ssd/")
    assert _reader("kda_roofline").read(artifacts) is None
    assert _reader("kda_time_pct").read(artifacts) is None


def test_the_kernels_calls_are_no_flash_calls():
    """``kda_fwd`` (two results, or three with the chunks' entry states, on
    rank 4) and ``kda_bwd`` (five) are not listed by ``lib/hlo.flash_calls``;
    a latent-attention call beside them is."""
    from lib import hlo

    def call(name, results):
        return (f'  %{name}.1 = ({", ".join(results)}) custom-call('
                f'bf16[1,32,16384,128]{{3,2,1,0}} %a), '
                f'custom_call_target="tpu_custom_call", metadata={{op_name='
                f'"jit(train_step)/jvp(Transformer)/blocks_1/ssm/kda/'
                f'{name}/pallas_call"}}')

    rows, state = "bf16[1,32,16384,128]{3,2,1,0}", "f32[1,32,128,128]{3,2,1,0}"
    text = "\n".join([
        call("kda_fwd", [rows, state]),
        call("kda_fwd", [rows, state, "f32[1,32,128,128,128]{4,3,2,1,0}"]),
        call("kda_bwd", [rows, rows, rows, "f32[1,32,16384,128]{3,2,1,0}",
                         "f32[1,128,16,2,128]{4,3,2,1,0}"]),
        call("mla_fwd", ["bf16[1,16384,4096]{2,1,0}",
                         "f32[1,32,16384,1]{3,2,1,0}"])])
    assert [c["kernel"] for c in hlo.flash_calls(text)] == ["mla_fwd"]


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_the_file_holds_every_published_number():
    """Every key of the catalog row's ``config`` (copied here: the catalog
    is no file of the repository) is in the configuration file with its
    published value, save the two in ``reduced`` that it has; the cut, the
    deployment and the ten assumed sentences are written out."""
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8,
        "num_hidden_layers": 27, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}
    config = _json(BENCH, "configs", "kimi-linear-48b-a3b.json")
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["changed"]) \
        == ["layer_types", "num_experts", "vocab_size"]
    for entry in config["changed"].values():
        assert set(entry) == {"source", "here", "why"}
    assert config["layer_types"] == config["kwargs"]["layer_types"] == [
        "kda_dense", "kda_sparse", "kda_sparse", "mla_sparse", "kda_sparse"]
    assert (config["num_experts"], config["num_experts_published"]) == (8, 256)
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (20480, 163840) and 20480 * 8 == 163840
    assert "expert-parallel 32" in config["deployment"]
    letters = sorted(text[:3] for text in config["assumed"].values()
                     if text.startswith("("))
    assert letters == [f"({c})" for c in "abcdefghij"]
    assert config["kwargs"]["experts_held"] == [0, 8]
    assert config["kwargs"]["seq_len"] == 16384
    assert config["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert set(config["check"]["tolerances"]) >= {
        "router_logits_rel", "moe_dropped", "chosen_not_top8_share",
        "kda_inputs_token_rel_max", "kda_out_token_rel_max",
        "kda_out_rel_rms", "kda_state_rel_rms", "mla_latent_token_rel_max",
        "mla_attn_token_rel_max", "loss_abs", "grad_rel_rms_all",
        "grad_rel_rms_worst"}
    assert "602,434,432" in config["state"]
    # the program's description reads the same widths, and writes the
    # published schedule out as the file says it
    from easydl_tpu.models.kimi_linear import SIZES, published_layer_types

    mine = SIZES["48b-a3b"]
    for key, value in mine.items():
        if key.startswith("kda_"):
            assert config["linear_attn_config"][key[4:]] == value, key
        elif key == "full_attn_layers":
            assert list(value) == config["linear_attn_config"][key]
        elif key != "num_experts":
            assert config[key] == value, key
    assert mine["num_experts"] == 256
    written = published_layer_types("48b-a3b")
    assert " ".join(written) in config["layer_types_published"]
    assert [i + 1 for i, kind in enumerate(written)
            if kind.startswith("kda")] \
        == config["linear_attn_config"]["kda_layers"]

"""Every per-layer reader of a steady cell on ONE saved traced run of that
cell on the chip (``fixtures/traced_<cell>.json``, made by
``fixtures/make_traced.py`` right behind the run, PR 50): each entry of
``BENCHMARK.json`` that lists the cell reads a number there, and a reader
that PR 50 renamed or merged without changing its definition gives the
number the reader it replaced gave on the same saved run — ``parent_values``,
read from the fixture by the parent commit's own readers — to the last digit."""

import glob
import importlib.util
import json
import os

import pytest

from conftest import BENCH, HERE, ROOT
from listed import reader

#: new name -> the names it took the place of (PR 50). One definition each:
#: ``flash_time_pct`` CHANGED where the backward is looped (the one-call
#: backward is in it again), ``flash_bwd_roofline`` is new (the silent
#: ``*_dq_`` / ``*_dkv_roofline`` pairs' place), so neither is held to the
#: parent's there.
RENAMED = {
    "mfu": ["model_flops_util", "hybrid_model_flops_util",
            "looplm_model_flops_util", "moe_model_flops_util",
            "zaya_model_flops_util", "joyai_mfu", "nemotron_mfu",
            "mellum_mfu"],
    "flash_fwd_roofline": [
        "flash_fwd_roofline", "hd128_flash_fwd_roofline",
        "gqa128_flash_fwd_roofline", "cca_flash_fwd_roofline",
        "mla_flash_fwd_roofline", "gqa2_flash_fwd_roofline",
        "gqa4_flash_fwd_roofline"],
    "flash_dq_roofline": ["flash_dq_roofline"],
    "flash_dkv_roofline": ["flash_dkv_roofline"],
    "attn_time_pct": ["attn_time_pct", "looplm_attn_time_pct",
                      "cca_attn_time_pct", "mla_attn_time_pct",
                      "gqa2_attn_time_pct"],
    "head_loss_time_pct": ["head_loss_time_pct", "looplm_head_time_pct",
                           "zaya_head_time_pct", "joyai_head_time_pct",
                           "nemotron_head_time_pct"],
    "moe_time_pct": ["moe_time_pct", "top1_moe_time_pct",
                     "joyai_moe_time_pct", "nemotron_moe_time_pct",
                     "mellum_moe_time_pct"],
    "experts_time_pct": ["moe_experts_time_pct", "top1_experts_time_pct",
                         "joyai_experts_time_pct", "relu2_experts_time_pct",
                         "top8_experts_time_pct"],
    "route_time_pct": ["moe_route_time_pct", "nemotron_router_time_pct"],
    "router_time_pct": ["zaya_router_time_pct", "softmax_router_time_pct"],
    "shared_expert_time_pct": ["nemotron_shared_expert_time_pct"],
    "band_attn_time_pct": ["swa_attn_time_pct", "swa1k_attn_time_pct"],
    "full_attn_time_pct": ["full_attn_time_pct", "gqa4_full_attn_time_pct"],
    "band_flash_time_pct": ["swa_flash_time_pct", "swa1k_flash_time_pct"],
    "band_flash_fwd_roofline": ["swa_flash_fwd_roofline",
                                "swa1k_flash_fwd_roofline"],
    "band_flash_dq_roofline": ["swa_flash_dq_roofline",
                               "swa1k_flash_dq_roofline"],
    "band_flash_dkv_roofline": ["swa_flash_dkv_roofline",
                                "swa1k_flash_dkv_roofline"],
    "ssm_time_pct": ["ssm_time_pct", "nemotron_ssm_time_pct"],
    "ssd_time_pct": ["ssd_time_pct", "g8_ssd_time_pct"],
    "ssd_roofline": ["ssd_roofline", "g8_ssd_roofline"],
    "conv1d_time_pct": ["conv1d_time_pct", "g8_conv1d_time_pct"],
    "rope_time_pct": ["rope_time_pct", "swa_full_rope_time_pct"],
    # in the unrolled cells every flash call was listed before too
    "flash_time_pct": ["flash_time_pct"],
}
#: the parent's ZAYA1 readers summed their operations' seconds over a SET of
#: instructions (``lib/zaya_names.pct_under_any``): the order, and with it the
#: last digits, followed the process's hash seed; these are held to 1e-12
SET_SUMMED = {"cca_attn_time_pct", "zaya_head_time_pct", "top1_moe_time_pct",
              "top1_experts_time_pct", "zaya_router_time_pct"}
FIXTURES = sorted(glob.glob(os.path.join(HERE, "fixtures", "traced_*.json")))


def _reader(name):
    return reader(name).read


def _load(path, monkeypatch):
    """The fixture's artifacts, with the trace's reduction stood in."""
    from lib import scope_names, scope_reduce

    with open(path) as f:
        fixture = json.load(f)
    reduced = fixture["reduced"]
    table = reduced["path_table"]
    found = dict(reduced, paths={op: table[i]
                                 for op, i in reduced["paths"].items()})
    monkeypatch.setattr(scope_reduce, "of_run",
                        lambda artifacts: found if artifacts.get(
                            "trace_summary") else None)
    monkeypatch.setattr(scope_reduce, "trace_file", lambda: path)
    monkeypatch.setattr(scope_names, "_self_seconds",
                        lambda path, mtime: fixture["self_seconds"])
    cell = os.path.basename(path)[len("traced_"):-len(".json")]
    return cell, fixture


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_every_listed_reader_reads_on_the_saved_run(path, monkeypatch):
    cell, fixture = _load(path, monkeypatch)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]
                  if cell in m.get("workloads", [cell])]
    assert listed
    for name in listed:
        value = _reader(name)(fixture["artifacts"])
        assert isinstance(value, (int, float)), (name, value)
        assert value == fixture["values"][name], name
        if name.endswith("_roofline") or "mfu" in name:
            assert 0.0 < value < 100.0, (name, value)


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_a_renamed_reader_reads_what_the_reader_it_replaced_read(
        path, monkeypatch):
    cell, fixture = _load(path, monkeypatch)
    # what the parent LISTED for the cell and read there (a reader of
    # another model's cell may read something else under a similar name)
    parent = {k: fixture["parent_values"][k] for k in fixture["parent_listed"]
              if isinstance(fixture["parent_values"][k], (int, float))}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if cell in m.get("workloads", [cell])}
    held = 0
    for new, olds in RENAMED.items():
        for old in olds:
            if old not in parent or new not in listed:
                continue
            if new == "flash_time_pct" and any(
                    c["kind"] == "bwd"
                    for c in fixture["artifacts"]["flash_calls"]):
                continue  # the definition changed here: PERF.md section 3
            value = _reader(new)(fixture["artifacts"])
            if old in SET_SUMMED:
                assert value == pytest.approx(parent[old], rel=1e-12), old
            else:
                assert value == parent[old], (old, new)
            held += 1
    assert held >= 3, held
    # and a reader whose name did not change reads what it read
    for name in listed - set(RENAMED):
        if name in parent:
            assert _reader(name)(fixture["artifacts"]) == parent[name], name


def test_fixtures_cover_every_steady_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    steady = set()
    for cell in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic",
                               cell["traffic"] + ".json")) as f:
            if json.load(f)["driver"] == "steady":
                steady.add(cell["name"])
    have = {os.path.basename(p)[len("traced_"):-len(".json")]
            for p in FIXTURES}
    assert have == steady


def test_the_fixture_keeps_what_ran_and_what_is_called():
    """``make_traced.build`` on a made-up run: an operation that did not run
    is dropped unless it is a flash call, paths go through a table."""
    spec = importlib.util.spec_from_file_location(
        "make_traced", os.path.join(HERE, "fixtures", "make_traced.py"))
    make = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(make)
    artifacts = {
        "device": {"count": 1, "kind": "TPU v5 lite"}, "timeline": [1],
        "flash_calls": [{"name": "flash_fwd.1"}],
        "trace_summary": {"busy_s": 1.0, "ops": {
            "flash_fwd.1": {"seconds": 0.5, "calls": 4.0},
            "fusion.2": {"seconds": 0.5, "calls": 4.0}}}}
    found = {"total_s": 1.0, "whole_paths": True, "paths": {
        "flash_fwd.1": "a/flash_fwd/pallas_call", "fusion.2": "a/ffn/mul",
        "fusion.3": "a/ffn/mul", "fusion.4": "a/ffn/add"}}
    seconds = {"fusion.2": 0.3, "fusion.3": 0.2, "fusion.4": 0.0}
    fixture = make.build("c", artifacts, found, seconds, {"x": 1.0})
    assert "timeline" not in fixture["artifacts"]
    assert set(fixture["reduced"]["paths"]) == {"flash_fwd.1", "fusion.2",
                                                "fusion.3"}
    assert len(fixture["reduced"]["path_table"]) == 2
    assert list(fixture["artifacts"]["trace_summary"]["ops"]) == [
        "flash_fwd.1"]
    assert fixture["self_seconds"] == {"fusion.2": 0.3, "fusion.3": 0.2}

"""What ``BENCHMARK.json`` lists for a cell, for the rehearsal tests: since
PR 50 a per-layer entry is a QUANTITY with the list of every cell that has it,
so a cell's readers are the entries that list it, wherever it stands there."""

import importlib.util
import json
import os

from conftest import BENCH, ROOT

REAL_JSON = os.path.join(ROOT, "BENCHMARK.json")
#: what a steady cell's traced run reads without a device: the host's clock,
#: the compile watch and the compiler's memory analysis
HOST_READERS = {"compile_s", "compiles_in_window", "step_ms_p50",
                "step_spread_pct", "step_hbm_gib"}


def entries(path, cell):
    with open(path) as f:
        return [m for m in json.load(f)["per_layer"]
                if cell in m.get("workloads", [cell])]


def names(path, cell):
    return {m["name"] for m in entries(path, cell)}


def device_derived(real_cell):
    """What only a device trace or a chip's peak can give the cell."""
    return names(REAL_JSON, real_cell) - HOST_READERS


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def check_rehearsal_file(test_json, cell, real_cell):
    """The rehearsal's file lists for its cell the readers the real file
    lists for the real one, entry for entry (no word on where a cell stands
    in a list or how many there are: the next PR appends its own), with one
    whole-step ``mfu`` among them and every entry but the compile's seconds
    moving ``tokens_per_s``."""
    mine = entries(REAL_JSON, real_cell)
    assert names(test_json, cell) == {m["name"] for m in mine}
    assert sum("mfu" in m["name"] for m in mine) == 1
    assert all(m["moves"] == "tokens_per_s" for m in mine
               if m["name"] != "compile_s")
    with open(REAL_JSON) as f:
        bench = json.load(f)
    assert len(bench["per_layer"]) <= 96
    assert all("workloads" in m for m in bench["per_layer"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    found, = (w for w in bench["workloads"] if w["name"] == real_cell)
    return found


def check_nothing_to_read(real_cell, configs):
    """On a run without a device trace — the CPU's, the parent's side of
    another model — no device-derived reader of the cell reads a number and
    none raises."""
    for config in configs:
        artifacts = {"config": config,
                     "traffic": {"global_batch": 2, "trace_steps": 4},
                     "device": {"platform": "cpu", "kind": "cpu"},
                     "check": {"ok": True}}
        for name in sorted(device_derived(real_cell)):
            assert reader(name).read(artifacts) is None, name

"""BENCHMARK.json against the contract's limits that can be checked here,
and against the benchmark's own files: every name resolves to a file."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # 2 + 14 x cells runs of run_seconds + 60, 180 s a cell to compile,
    # 1200 s spare, with the full 24 cells, inside 43200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert sorted(c["reduced"]) == sorted(data["reduced"])
        assert sorted(data["changed"]) == sorted(data["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            # never a width
            assert not re.search(r"(_dim|_rank|n_embd|n_inner|n_head|hidden|"
                                 r"intermediate)", key), key
        for text in (c["why"], c["source"]):
            assert 1 <= len(text) <= 200 and "\n" not in text


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           mix["driver"] + ".py"))
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert 1 <= len(bench["end_to_end"]) <= 16
    # the contract allows 128; since PR 50 an entry is a QUANTITY with the
    # list of every cell that has it, and a third of the room stays free
    # for the quantities a later configuration brings
    assert 1 <= len(bench["per_layer"]) <= 96
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert os.path.exists(os.path.join(BENCH, "end_to_end",
                                           m["name"] + ".py"))
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
        # reported only where the metric it moves is
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in bench["per_layer"])


def test_run_py_branches_on_no_name(bench):
    """run.py holds no cell, configuration, traffic, driver or metric name."""
    with open(os.path.join(BENCH, "run.py")) as f:
        code = f.read()
    code = code.split('"""', 2)[2]  # the module docstring explains by name
    names = {w["name"] for w in bench["workloads"]}
    names |= {c["name"] for c in bench["configs"]}
    names |= {w["traffic"] for w in bench["workloads"]}
    names |= {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    names |= {f[:-3] for f in os.listdir(os.path.join(BENCH, "drivers"))
              if f.endswith(".py")}
    for name in names:
        assert f'"{name}"' not in code and f"'{name}'" not in code, name


def _reader_files():
    directory = os.path.join(BENCH, "layer_metrics")
    return {f[:-3]: os.path.join(directory, f)
            for f in os.listdir(directory) if f.endswith(".py")}


def test_one_reader_file_an_entry_and_one_entry_a_reader_file(bench):
    assert set(_reader_files()) == {m["name"] for m in bench["per_layer"]}
    # every entry says where it reads; none stands for every cell to come
    assert all(m.get("workloads") for m in bench["per_layer"])


def test_no_reader_knows_a_configuration(bench):
    """A reader is named for a quantity and TOLD what differs by cell
    (``lib/told.py``): it imports no configuration's module by name and
    compares no configuration's, cell's or model type's name; and the shared
    files the readers go through list no configuration either. A later PR
    brings a quantity to its cell by adding a module and appending its cell
    to the lists."""
    modules = {f[:-3] for f in os.listdir(os.path.join(BENCH, "lib"))
               if f.startswith(("cell_", "check_", "reference_"))}
    names = {c["name"] for c in bench["configs"]}
    names |= {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        names.add(data.get("model_type") or data["factory"])
        assert data["readers"]["module"] in modules, c["name"]
    shared = [os.path.join(BENCH, "lib", f"{name}.py") for name in (
        "told", "scope_names", "scope_reduce", "hlo", "trace_reduce")]
    for path in list(_reader_files().values()) + shared:
        with open(path) as f:
            code = f.read()
        for module in modules:
            assert not re.search(rf"\b{module}\b", code), (path, module)
        assert "model_type" not in code, path
        for name in names:
            assert f'"{name}"' not in code and f"'{name}'" not in code, (
                path, name)


def test_a_cell_is_listed_only_where_its_module_states_the_quantity(bench):
    """An entry that reads through a configuration's module (the told
    quantities) lists a cell only where that module states it."""
    import importlib

    configs = {c["name"]: c for c in bench["configs"]}
    readers = _reader_files()
    for cell in bench["workloads"]:
        with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
            config = json.load(f)
        module = importlib.import_module(
            f"lib.{config['readers']['module']}")
        stated = set(module.scopes(config)) | set(module.kernels(config))
        for m in bench["per_layer"]:
            if cell["name"] not in m["workloads"]:
                continue
            with open(readers[m["name"]]) as f:
                code = f.read()
            if "told.share_pct" in code or "told.kernel_roofline" in code:
                assert m["name"] in stated, (cell["name"], m["name"])
            if "told.ssd_roofline" in code:
                assert hasattr(module, "ssd_cost"), cell["name"]
            if "told.mfu_pct" in code:
                assert hasattr(module, "train_flops_per_token")

"""The steady driver rehearsed on the CPU with the looped model: the
``ouro-test`` configuration (three layers, four passes, 64 wide) through
``run.py`` with its own ``BENCHMARK.ouro-test.json``, ``check_ouro`` deciding
``correct`` and the new readers listed; and ``BENCHMARK.json``'s new cell
refusing to run without a chip."""

import json
import os

import pytest

from conftest import HERE, ROOT
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.ouro-test.json")
CELL = "ouro-test.steady-4k-b4"
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = {"looplm_model_flops_util", "looplm_attn_time_pct",
                  "hd128_flash_time_pct", "rope_time_pct",
                  "sandwich_norm_time_pct", "looplm_head_time_pct",
                  "hd128_flash_fwd_roofline", "hd128_flash_dq_roofline",
                  "hd128_flash_dkv_roofline", "device_idle_pct"}


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, {"compile_s", "compiles_in_window", "step_ms_p50", "step_spread_pct",
         "step_hbm_gib"}),
])
def test_looplm_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483653", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    assert "'state_rel_rms_pass_3'" in proc.stdout
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    with open(TEST_JSON) as f:
        rehearsal = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert DEVICE_DERIVED <= {m["name"] for m in rehearsal["per_layer"]}
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == ["ouro-2.6b.steady-4k-b4"]}
    assert mine == DEVICE_DERIVED - {"device_idle_pct"}


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", "ouro-2.6b.steady-4k-b4", "--seed", "0",
                   "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")

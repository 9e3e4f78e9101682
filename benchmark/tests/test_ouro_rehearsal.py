"""The steady driver rehearsed on the CPU with the looped model: the
``ouro-test`` configuration (three layers, four passes, 64 wide) through
``run.py`` with its own ``BENCHMARK.ouro-test.json``, ``check_ouro`` deciding
``correct`` and the new readers listed; and ``BENCHMARK.json``'s new cell
refusing to run without a chip."""

import os

import pytest

from conftest import HERE
from listed import check_rehearsal_file, device_derived
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.ouro-test.json")
CELL = "ouro-test.steady-4k-b4"
REAL_CELL = "ouro-2.6b.steady-4k-b4"
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL)


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
    assert {"mfu", "attn_time_pct", "flash_time_pct", "rope_time_pct",
            "sandwich_norm_time_pct", "head_loss_time_pct",
            "flash_fwd_roofline", "flash_bwd_roofline",
            "device_idle_pct"} <= DEVICE_DERIVED
    assert not {"flash_dq_roofline", "flash_dkv_roofline"} & DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-4k-b4"


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")

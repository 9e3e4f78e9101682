"""The steady driver rehearsed on the CPU with the hybrid: the ``granite-test``
configuration (two Mamba-2 layers and one attention layer, 64 wide) through
``run.py`` with its own ``BENCHMARK.granite-test.json``, the new check module
deciding ``correct`` and the new readers listed; and ``BENCHMARK.json``'s new
cell refusing to run without a chip."""

import os

import pytest

from conftest import HERE
from listed import check_rehearsal_file, device_derived
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.granite-test.json")
CELL = "granite-test.steady-4k"
REAL_CELL = "granite-4.0-h-micro.steady-4k"
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, {"compile_s", "compiles_in_window", "step_ms_p50", "step_spread_pct",
         "step_hbm_gib"}),
])
def test_hybrid_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483653", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"ssm_time_pct", "ssd_time_pct", "conv1d_time_pct", "ssd_roofline",
            "mfu", "flash_fwd_roofline", "flash_bwd_roofline",
            "device_idle_pct"} <= DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-4k"


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", "granite-4.0-h-micro.steady-4k", "--seed",
                   "0", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")

"""The steady driver rehearsed on the CPU with Laguna's test size (five
layers: full + dense, three window + sparse, full + sparse; 4 of 16 experts
held) through ``run.py`` with its own ``BENCHMARK.laguna-test.json``,
``check_laguna`` deciding ``correct`` and the new readers listed; and
``BENCHMARK.json``'s new cell refusing to run without a chip."""

import os

import pytest

from conftest import HERE
from listed import (check_nothing_to_read, check_rehearsal_file,
                    device_derived)
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.laguna-test.json")
CELL = "laguna-test.steady-8k-b2"
REAL_CELL = "laguna-xs.2.steady-8k-b2"
#: what only a device trace or a chip's peak can give
DEVICE_DERIVED = device_derived(REAL_CELL)


@pytest.mark.parametrize("trace,expect", [
    (0, {"tokens_per_s", "setup_s"}),
    (1, {"compile_s", "compiles_in_window", "step_ms_p50", "step_spread_pct",
         "step_hbm_gib"}),
])
def test_laguna_rehearsal(trace, expect):
    proc = run_py(["--benchmark-json", TEST_JSON, "--workload", CELL,
                   "--seed", "2147483653", "--seconds", "2", "--trace",
                   str(trace)])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    assert "reference check {'ok': True" in proc.stdout
    assert "'state_rel_rms_layer_4'" in proc.stdout
    # the expert layers' counters reach the check (on its seeded sequences)
    # and Trainer.train_step's metrics; the steady driver keeps a step's
    # loss alone, so no reader reports them from the window
    assert "'moe_dropped': 0.0" in proc.stdout
    assert "'chosen_not_top8_share': 0.0" in proc.stdout
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_rehearsal_file_lists_the_new_readers():
    assert {"mfu", "moe_time_pct", "experts_time_pct", "route_time_pct",
            "band_attn_time_pct", "full_attn_time_pct", "band_flash_time_pct",
            "band_flash_fwd_roofline", "band_flash_dq_roofline",
            "band_flash_dkv_roofline", "flash_fwd_roofline",
            "flash_bwd_roofline", "flash_time_pct", "rope_time_pct",
            "device_idle_pct"} <= DEVICE_DERIVED
    cell = check_rehearsal_file(TEST_JSON, CELL, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-8k-b2"


def test_the_new_readers_find_nothing_in_a_program_without_the_names():
    """On the parent's side of a traced run the new readers return nothing
    and do not raise: artifacts of another model, no trace, no counters."""
    check_nothing_to_read(REAL_CELL, (
        {"layer_types": ["full_attention"]},
        {"readers": {"module": "cell_laguna"}, "sliding_window": 512,
         "layer_types": ["sliding_attention"], "mlp_layer_types": ["sparse"],
         "kwargs": {"seq_len": 64}}))


def test_no_chip_no_metric_for_the_new_cell():
    proc = run_py(["--workload", REAL_CELL, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")

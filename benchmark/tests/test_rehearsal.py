"""Each driver rehearsed on the CPU at the ``test`` size (2 layers, d_model
128), through ``run.py`` as the driver of the check would call it, from
files that no cell of BENCHMARK.json references; and the refusals: no chip,
no metric; no program beside the benchmark, no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

TEST_JSON = os.path.join(HERE, "BENCHMARK.test.json")
#: names whose value exists only on a device
DEVICE_DERIVED = {"mfu", "flash_time_pct", "flash_roofline",
                  "device_idle_pct", "collective_pct",
                  "collective_exposed_pct"}


def run_py(args, cwd=ROOT, script=os.path.join(BENCH, "run.py"),
           devices=1, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu", EASYDL_COMPILE_CACHE="off",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,devices,seconds,trace,expect", [
    ("gpt2-test.steady", 1, 2, 0, {"tokens_per_s", "setup_s"}),
    ("gpt2-test.steady", 1, 2, 1,
     {"compile_s", "compiles_in_window", "step_ms_p50", "step_spread_pct",
      "step_hbm_gib"}),
    ("gpt2-test.fsdp4-steady", 4, 2, 0, {"tokens_per_s", "setup_s"}),
    ("gpt2-test.kill-resume", 1, 25, 0,
     {"tokens_per_s", "setup_s"}),
    ("gpt2-test.kill-resume", 1, 25, 1,
     {"resume_s", "save_stall_s", "resume_detect_s", "extra_generations",
      "resume_boot_s",
      "resume_first_step_s", "loop_overhead_pct", "resume_restore_s",
      "ckpt_commit_s"}),
])
def test_driver_rehearsal(cell, devices, seconds, trace, expect):
    line = last_line(run_py(
        ["--benchmark-json", TEST_JSON, "--workload", cell, "--seed", "3",
         "--seconds", str(seconds), "--trace", str(trace)], devices=devices))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    # the reference agrees with the program at this size (steady), the
    # replayed step repeats its loss (kill-resume)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & DEVICE_DERIVED
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    if trace and "kill-resume" in cell:
        assert line["metrics"]["extra_generations"]["value"] == 0
        assert line["device"]["busy_s"] > 0 and line["breakdown"]["idle_gaps"]
    if trace and "steady" in cell:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("cell", ["gpt2-medium.steady",
                                  "gpt2-medium.kill-resume",
                                  "gpt2-medium.reshape-resume",
                                  "gpt2-xl.fsdp4-steady"])
def test_no_chip_no_metric(cell):
    """Off the TPU a real cell exits non-zero and prints no result line."""
    proc = run_py(["--workload", cell, "--seed", "0", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode != 0
    assert "tpu" in proc.stderr.lower()
    assert not [ln for ln in proc.stdout.splitlines() if '"metrics"' in ln]


def test_benchmark_alone_in_a_directory_prints_nothing(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = run_py(["--workload", "gpt2-medium.steady", "--seed", "0",
                   "--seconds", "1", "--trace", "0"], cwd=str(tmp_path),
                  script=str(tmp_path / "benchmark" / "run.py"))
    assert proc.returncode != 0 and not proc.stdout.strip()
    assert "easydl_tpu" in proc.stderr


def test_discovery_by_adding_files_only(tmp_path):
    """A configuration, a traffic mix, a layer-metric reader and a cell,
    added as NEW files and entries in a copy, are found and run by
    ``run.py`` with no edit to a file that was there."""
    root = tmp_path
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    os.symlink(os.path.join(ROOT, "easydl_tpu"), root / "easydl_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}

    with open(os.path.join(BENCH, "configs", "gpt2-test.json")) as f:
        config = json.load(f)
    config["name"] = "gpt2-new"
    (root / "benchmark/configs/gpt2-new.json").write_text(json.dumps(config))
    with open(os.path.join(BENCH, "traffic", "steady-test.json")) as f:
        mix = json.load(f)
    mix.update(global_batch=4, grad_accum=1)
    (root / "benchmark/traffic/tiny-batch.json").write_text(json.dumps(mix))
    (root / "benchmark/layer_metrics/last_loss.py").write_text(
        '"""trainer: the loss of the window\'s last step."""\n\n\n'
        'def read(artifacts):\n'
        '    losses = artifacts.get("losses")\n'
        '    return losses[-1] if losses else None\n')
    with open(TEST_JSON) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "gpt2-new", "source": "rehearsal",
                             "file": "benchmark/configs/gpt2-new.json",
                             "reduced": [], "why": "discovery test"})
    bench["workloads"].append({"name": "gpt2-new.tiny-batch",
                               "config": "gpt2-new", "traffic": "tiny-batch",
                               "chips": 1, "why": "discovery test"})
    bench["per_layer"].append({
        "name": "last_loss", "unit": "nats", "better": "lower",
        "source": "host_clock", "layer": "trainer", "moves": "tokens_per_s",
        "workloads": ["gpt2-new.tiny-batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = last_line(run_py(
        ["--workload", "gpt2-new.tiny-batch", "--seed", "5", "--seconds",
         "1", "--trace", "1"], cwd=str(root),
        script=str(root / "benchmark" / "run.py")))
    assert line["correct"] is True
    # the new reader ran; readers listed for other cells did not
    assert line["metrics"]["last_loss"]["unit"] == "nats"
    assert "resume_boot_s" not in line["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "a file that was there changed"

"""The nine readers of the elastic worker's own account (PR 33) on made-up
records and timelines whose answers can be worked out by hand, on a run
recorded before any of their fields existed (each gives None, or what the
old phases already said), and through ``run.py`` at the ``test`` size."""

import importlib.util
import json
import os

import pytest

from conftest import BENCH, HERE
from lib import worker_records
from test_rehearsal import last_line, run_py

SHARES = ("worker_input_wait_pct", "worker_dispatch_pct",
          "worker_device_wait_pct", "worker_gap_pct")
NINE = SHARES + ("commit_drag_pct", "resume_exec_s", "resume_jax_import_s",
                 "resume_step_program_trace_lower_s",
                 "resume_other_programs_s")


def read(name, artifacts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(artifacts)


def rec(step, gen, t, in_flight=False, **inside):
    """A step of 0.8 s after a gap of 0.2: 0.01 waiting for input, 0.03
    placing the batch, 0.06 dispatching, 0.7 waiting for the device."""
    fields = dict(gap_s=0.2, data_s=0.01, shard_s=0.03, dispatch_s=0.06,
                  wait_s=0.7, commit_in_flight=in_flight)
    fields.update(inside)
    return dict({"step": step, "generation": gen, "t": t, "loss": 5.0,
                 "step_time_s": 0.8}, **fields)


def made_up(interval_under_commit=1.0):
    """N = 5: the window opens at t=104 as C0 commits; steps 6..10 one
    second apart; S1 at step 10 stalls the loop; 11..13 run beside its
    commit, ``interval_under_commit`` apart; the kill at 116.5; generation 2
    resumes from step 5 with its first record at t=140."""
    records = [rec(s, 1, 100.0 + s) for s in range(1, 11)]
    t = 114.0
    for s in (11, 12, 13):
        records.append(rec(s, 1, t, in_flight=True,
                           gap_s=interval_under_commit - 0.8))
        t += interval_under_commit
    records += [rec(6, 2, 140.0, step_time_s=2.0, wait_s=1.9),
                rec(7, 2, 141.0), rec(8, 2, 142.0), rec(9, 2, 143.0)]
    timeline = [
        {"t": 90.0, "phase": "spawn", "gen": 1},
        {"t": 90.4, "phase": "worker_main_start", "gen": 1},
        {"t": 92.0, "phase": "jax_imported", "gen": 1},
        {"t": 122.0, "phase": "spawn", "gen": 2},
        {"t": 122.75, "phase": "worker_main_start", "gen": 2,
         "since_exec_s": 0.7},
        {"t": 125.0, "phase": "jax_imported", "gen": 2},
        {"t": 125.0, "phase": "dist_init_done", "gen": 2},
        {"t": 132.0, "phase": "devices_ready", "gen": 2},
        {"t": 134.5, "phase": "trainer_built", "gen": 2},
        {"t": 136.0, "phase": "restored", "gen": 2, "step": 5},
        {"t": 140.0, "phase": "first_step_done", "gen": 2,
         "trace_s": 3.2, "lower_s": 0.75, "backend_s": 0.9,
         "programs": [
             {"name": "train_step", "trace_s": 3.0, "lower_s": 0.5,
              "backend_s": 0.6, "cache_retrieval_s": 0.5},
             {"name": "convert_element_type", "trace_s": 0.125,
              "lower_s": 0.125, "backend_s": 0.25,
              "cache_retrieval_s": 0.0}],
         "other_programs_s": 0.25},
    ]
    return {"records": records, "timeline": timeline, "t_open": 104.0,
            "t_close": 150.0, "t_kill": 116.5 + 2 * (
                interval_under_commit - 1.0), "killed_generation": 1,
            "save_steps": [5, 10]}


def test_the_four_shares_are_parts_of_one_whole():
    run = made_up()
    # steps 4..10 are in the window: 6 pairs less 5->6 (C0's save); not
    # 10->11 (S1's save); 11->12, 12->13; three of generation 2: ten
    # intervals of 1 s
    pairs = worker_records.window_pairs(run)
    assert len(pairs) == 5 + 2 + 3
    shares = {name: read(name, run) for name in SHARES}
    assert shares == {
        "worker_input_wait_pct": pytest.approx(1.0),
        "worker_dispatch_pct": pytest.approx(3.0 + 6.0),
        "worker_device_wait_pct": pytest.approx(70.0),
        "worker_gap_pct": pytest.approx(20.0)}
    assert sum(shares.values()) == pytest.approx(100.0)
    # the same whole as loop_overhead_pct's: its medians read the gap's 20
    assert read("loop_overhead_pct", run) == pytest.approx(
        shares["worker_gap_pct"])


def test_an_interval_that_stands_out_moves_the_sum_not_the_median():
    run = made_up()
    slow = next(r for r in run["records"]
                if r["generation"] == 2 and r["step"] == 8)
    for r in run["records"]:  # one frozen second before step 8
        if r["generation"] == 2 and r["step"] >= 8:
            r["t"] += 1.0
    slow["gap_s"] += 1.0
    assert read("loop_overhead_pct", run) == pytest.approx(20.0)
    assert read("worker_gap_pct", run) == pytest.approx(100 * 3.0 / 11.0)


def test_commit_drag_is_the_median_interval_under_a_commit_over_the_rest():
    assert read("commit_drag_pct", made_up()) == pytest.approx(0.0)
    assert read("commit_drag_pct", made_up(1.02)) == pytest.approx(2.0)
    run = made_up(1.02)
    for r in run["records"]:
        r["commit_in_flight"] = False
    assert read("commit_drag_pct", run) is None  # no step beside a commit


def test_the_boots_legs_tile_resume_boot_s():
    run = made_up()
    legs = {name: read(name, run) for name in (
        "resume_exec_s", "resume_jax_import_s", "resume_runtime_s",
        "resume_build_s")}
    assert legs == {"resume_exec_s": pytest.approx(0.75),
                    "resume_jax_import_s": pytest.approx(2.25),
                    "resume_runtime_s": pytest.approx(7.0),
                    "resume_build_s": pytest.approx(2.5)}
    assert sum(legs.values()) == pytest.approx(read("resume_boot_s", run))


def test_compiles_by_program_split_resume_trace_lower_s():
    run = made_up()
    step = read("resume_step_program_trace_lower_s", run)
    other = read("resume_other_programs_s", run)
    assert step == pytest.approx(3.5)
    # the other program's 0.125 + 0.125 + 0.25 and the rest's 0.25
    assert other == pytest.approx(0.75)
    assert read("resume_trace_lower_s", run) == pytest.approx(3.95)
    first = run["timeline"][-1]
    first["programs"] = first["programs"][1:]  # no step program among them
    assert read("resume_step_program_trace_lower_s", run) is None
    assert read("resume_other_programs_s", run) == pytest.approx(0.75)


@pytest.mark.parametrize("name", NINE)
def test_no_kill_or_no_records_no_number(name):
    run = dict(made_up(), t_kill=None)
    if name.startswith("resume_"):
        assert read(name, run) is None
    assert read(name, {"correct": True}) is None  # a steady cell's


@pytest.mark.parametrize("name", NINE)
def test_a_run_recorded_before_the_fields_existed(name):
    """PR 22's chip recording: no ``data_s`` ... ``commit_in_flight`` on a
    record, no ``programs`` on ``first_step_done``; ``worker_main_start``
    and ``jax_imported`` were on the timeline already."""
    with open(os.path.join(HERE, "fixtures", "chip_kill_resume.json")) as f:
        run = json.load(f)
    value = read(name, run)
    if name == "resume_exec_s":
        assert 0.2 < value < 3.0
    elif name == "resume_jax_import_s":
        assert 0.5 < value < 6.0
    else:
        assert value is None


def test_rehearsal_prints_all_nine():
    """``BENCHMARK.test.json``'s kill-resume cell and its entries with the
    nine appended (under a name of its own: a cell's name is its run
    directory, and the old rehearsals may run beside this one): the worker
    of this checkout leaves every field, so each reader finds its number,
    on the CPU and at the test size."""
    line = last_line(run_py(
        ["--benchmark-json",
         os.path.join(HERE, "BENCHMARK.worker-test.json"), "--workload",
         "gpt2-test.worker-kill-resume", "--seed", "2147483659", "--seconds",
         "25", "--trace", "1"]))
    assert line["correct"] is True and line["failed"] == 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NINE) <= set(metrics)
    shares = [metrics[name] for name in SHARES]
    assert all(s >= 0 for s in shares)
    # four parts of one whole, less the statements between the spans
    assert 95.0 < sum(shares) <= 100.0 + 1e-6
    legs = sum(metrics[name] for name in (
        "resume_exec_s", "resume_jax_import_s")) + metrics["resume_boot_s"]
    assert metrics["resume_exec_s"] > 0 and legs > 0
    assert metrics["resume_jax_import_s"] > 0
    assert metrics["resume_step_program_trace_lower_s"] > 0
    assert metrics["resume_other_programs_s"] >= 0


def test_the_test_files_entries_are_the_real_files_entries():
    """``BENCHMARK.worker-test.json`` rehearses what ``BENCHMARK.json``
    declares: the nine entries differ by the cell's name alone."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(os.path.join(HERE, "BENCHMARK.worker-test.json")) as f:
        test = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NINE:
        assert real[name]["workloads"][0] == "gpt2-medium.kill-resume"
        assert dict(real[name], workloads=None) == dict(test[name],
                                                        workloads=None)

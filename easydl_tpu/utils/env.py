"""The EASYDL_* knob registry + process-environment recipes.

Every environment knob the fleet reads is DECLARED here — name, type,
default, one-line purpose — and read through the typed accessors
(:func:`knob_str` / :func:`knob_int` / :func:`knob_float` /
:func:`knob_bool` / :func:`knob_raw`). The declaration is load-bearing
three ways:

* easylint's ``knob-registry`` rule (analysis/rules/knobs.py) rejects any
  inline ``os.environ`` read of an ``EASYDL_*`` literal outside this
  module, and rejects accessor calls whose name is not declared — a
  typo'd knob fails in lint, not silently in production;
* the doc-sync test (tests/test_easylint.py) asserts the
  ``docs/operations.md`` knob table and ``KNOB_DECLS`` agree both ways,
  so the operator docs cannot rot;
* the accessors give every knob ONE parsing convention (booleans via the
  flag grammar below, numbers via int()/float()) and one default,
  instead of per-call-site drift.

``KNOB_DECLS`` is a pure literal tuple on purpose: the static analyzer
reads it with ``ast.literal_eval`` — no import side effects required. A
trailing ``*`` declares a name FAMILY (``EASYDL_METRICS_PORT_<COMP>``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Mapping, Optional

# --------------------------------------------------------------- registry
#: (name, type, default, help). type ∈ {str,int,float,bool}; default None
#: means REQUIRED — reading it when unset raises KeyError, matching the
#: old `env["EASYDL_RANK"]` behavior for the agent→worker IPC variables.
KNOB_DECLS = (
    # -- identity / job wiring (set by launchers, read by services) ------
    ("EASYDL_WORKDIR", "str", None,
     "Job working directory: journals, WAL roots, checkpoints, metrics "
     "files, timelines all live under it."),
    ("EASYDL_JOB", "str", "",
     "Job name a pod belongs to (controller process/kube backends)."),
    ("EASYDL_POD_NAME", "str", "",
     "Pod name injected by the controller backends (PS pod identity)."),
    ("EASYDL_POD_ROLE", "str", "",
     "Pod role (ps/master/agent/serve) injected by controller backends."),
    ("EASYDL_AGENT_ID", "str", "",
     "Agent identity passed to worker subprocesses (chaos windows, "
     "metrics file naming)."),
    ("EASYDL_REPLACES", "str", "",
     "Pod name a rescue PS shard replaces (claims its WAL + shard slot)."),
    ("EASYDL_RESHARD_DEST", "bool", False,
     "Marks a PS pod as a live-reshard destination (skips rescue probe)."),
    # -- agent -> worker IPC (required where read) -----------------------
    ("EASYDL_RANK", "int", None,
     "Worker rank within the generation (agent->worker spawn env)."),
    ("EASYDL_WORLD", "int", None,
     "World size of the generation (agent->worker spawn env)."),
    ("EASYDL_COORD", "str", None,
     "jax.distributed coordinator address (agent->worker spawn env)."),
    ("EASYDL_GEN", "int", None,
     "Membership generation the worker belongs to."),
    ("EASYDL_METRICS", "str", None,
     "Per-agent metrics JSONL path the worker appends step reports to."),
    ("EASYDL_MESH", "str", "",
     "Mesh shape key ('dp=2,fsdp=2,tp=2') the elastic master decided for "
     "this generation; '' = take the static job-config mesh."),
    ("EASYDL_TIMELINE", "str", "",
     "Recovery-timeline JSONL path (phase boundary events)."),
    ("EASYDL_GO_FILE", "str", "",
     "Rendezvous gate file: worker blocks until it appears."),
    ("EASYDL_WARM_FILE", "str", "",
     "Warm-standby gate file: standby imports+compiles, then blocks."),
    ("EASYDL_MASTER_WAIT_S", "float", 600.0,
     "How long an agent waits for a master before giving up."),
    # -- logging / metrics exporter --------------------------------------
    ("EASYDL_LOG_LEVEL", "str", "INFO",
     "Root logger level for every easydl_tpu process."),
    ("EASYDL_METRICS_HOST", "str", "",
     "Bind host for /metrics exporters (default localhost)."),
    ("EASYDL_METRICS_PORT", "int", 0,
     "Exporter port for all components; 0 picks a free port; "
     "off/disabled/negative disables."),
    ("EASYDL_METRICS_PORT_*", "int", 0,
     "Per-component exporter port override; wins over "
     "EASYDL_METRICS_PORT."),
    ("EASYDL_METRICS_PORT_MASTER", "int", 0,
     "Exporter port for the elastic master."),
    ("EASYDL_METRICS_PORT_AGENT", "int", 0,
     "Exporter port for the elastic agent."),
    ("EASYDL_METRICS_PORT_PS", "int", 0,
     "Exporter port for PS shard pods."),
    ("EASYDL_METRICS_PORT_BRAIN", "int", 0,
     "Exporter port for the Brain service."),
    ("EASYDL_METRICS_PORT_CONTROLLER", "int", 0,
     "Exporter port for the controller/operator."),
    ("EASYDL_METRICS_PORT_SERVE", "int", 0,
     "Exporter port for serving replicas."),
    # -- tracing ----------------------------------------------------------
    ("EASYDL_TRACE", "str", "",
     "Arms distributed tracing; ''/0/off/false/no/disabled/none = off."),
    ("EASYDL_TRACE_CONTEXT", "str", "",
     "Injected parent span context (subprocess hop of propagation)."),
    ("EASYDL_TRACE_PROC", "str", "",
     "Process name override for the flight recorder."),
    ("EASYDL_TRACE_MAX_BYTES", "int", 8_388_608,  # 8 MiB
     "Flight-recorder ring size per process."),
    ("EASYDL_TRACE_STEP_EVERY", "int", 25,
     "Worker traces every Nth train step."),
    # -- parameter server -------------------------------------------------
    ("EASYDL_PS_WAL", "bool", True,
     "Push write-ahead log on/off (zero-loss recovery, PR 6)."),
    ("EASYDL_PS_WAL_SEGMENT_BYTES", "int", 33_554_432,  # 32 MiB
     "WAL segment roll size."),
    ("EASYDL_PS_WAL_SYNC_S", "float", 0.2,
     "WAL fsync cadence; 0 = fsync every append."),
    ("EASYDL_PS_FENCE_CHECK_S", "float", 0.5,
     "Zombie self-check cadence against the registry epoch."),
    ("EASYDL_PS_PROBE_TIMEOUT_S", "float", 5.0,
     "Rescue probe per-attempt timeout."),
    ("EASYDL_PS_PROBE_RETRIES", "int", 2,
     "Rescue probe attempts before declaring a shard dead."),
    ("EASYDL_PS_CHUNK_BYTES", "int", 1_048_576,  # 1 MiB
     "Client-side pull/push chunking target."),
    ("EASYDL_PS_COALESCE", "bool", True,
     "Duplicate-id coalescing on pull (trainer path defaults off)."),
    ("EASYDL_PS_RAW_IDS", "bool", True,
     "Zero-copy raw-bytes id wire format (falls back per shard)."),
    ("EASYDL_PS_PULL_FP16", "bool", False,
     "Negotiate fp16 pull payloads (halves the wire)."),
    ("EASYDL_PS_PULL_I8", "bool", False,
     "Negotiate int8 pull payloads (per-row symmetric quantization, "
     "~0.25x the f32 wire; serving replicas only — the trainer keeps "
     "f32)."),
    ("EASYDL_PS_SHM", "bool", False,
     "Zero-copy shared-memory pull transport: shards mirror tables into "
     "named shm segments, co-located clients gather rows directly "
     "(seqlock-validated) and fall back to gRPC on any mismatch."),
    ("EASYDL_PS_SHM_MAX_MB", "int", 256,
     "Per-table shm mirror capacity cap; a table outgrowing it revokes "
     "the mirror (clients fall back to the wire)."),
    ("EASYDL_PS_STORE_LOOP", "bool", False,
     "Force the python reference row-apply loop (bench comparisons)."),
    ("EASYDL_PS_TIER_HOT_MB", "int", 0,
     "Hot-tier byte budget per shard for the two-tier native store; 0 = "
     "single-tier (no cold spill)."),
    ("EASYDL_PS_TIER_COLD_MB", "int", 4096,
     "Cold-tier mmap file capacity per table (under the shard workdir)."),
    ("EASYDL_PS_TIER_PROMOTE_INTERVAL_S", "float", 2.0,
     "Tier maintenance cadence: decay frequencies, demote cold hot rows, "
     "promote warm cold rows."),
    ("EASYDL_PS_TIER_DECAY", "float", 0.9,
     "Per-tick multiplicative access-frequency decay (ages out "
     "yesterday's hot set)."),
    # -- cross-cell failover (cell/) --------------------------------------
    ("EASYDL_CELL_STANDBY_WORKDIR", "str", "",
     "Standby cell workdir the WAL shipper replicates into; '' = no "
     "standby configured."),
    ("EASYDL_CELL_SHIP_INTERVAL_S", "float", 0.5,
     "Cross-cell ship pass cadence (bounds the async-replication RPO)."),
    ("EASYDL_CELL_LAG_SLO_BYTES", "int", 4_194_304,  # 4 MiB
     "Replication-lag SLO the promotion decision records breaches "
     "against (easydl_cell_replication_lag gauge)."),
    ("EASYDL_CELL_RTO_BUDGET_S", "float", 60.0,
     "Promotion RTO budget: fence -> standby tier serving scores."),
    ("EASYDL_PS_SPLIT_HOT_RATIO", "float", 1.5,
     "Hot-shard split trigger: shard rows vs mean ratio."),
    ("EASYDL_PS_SPLIT_MIN_ROWS", "float", 100_000.0,
     "Minimum total rows before split decisions engage."),
    ("EASYDL_PS_SPLIT_MAX_SHARDS", "int", 64,
     "Upper bound on PS shard fan-out from auto-splits."),
    ("EASYDL_PS_SPLIT_ACCESS_RATIO", "float", 2.0,
     "Max/mean per-shard access ratio that counts as hot-working-set "
     "skew (the two-tier split trigger)."),
    # -- serving ----------------------------------------------------------
    ("EASYDL_SERVE_TARGET_QPS", "float", 500.0,
     "Per-replica QPS target for the autoscale policy."),
    ("EASYDL_SERVE_P99_BUDGET_S", "float", 0.050,
     "p99 latency budget for the autoscale policy."),
    ("EASYDL_SERVE_MIN_REPLICAS", "int", 1,
     "Autoscale floor for serving replicas."),
    ("EASYDL_SERVE_MAX_REPLICAS", "int", 64,
     "Autoscale ceiling for serving replicas."),
    # -- serve fleet router ------------------------------------------------
    ("EASYDL_SERVE_HEDGE_BUDGET", "float", 0.1,
     "Hedged-request budget: max fraction of recent routed requests that "
     "may carry a hedge (a sick fleet must not double its own load); "
     "<= 0 disables hedging."),
    ("EASYDL_SERVE_HEDGE_MIN_MS", "float", 5.0,
     "Floor for the p95-derived hedge delay."),
    ("EASYDL_SERVE_HEDGE_MAX_MS", "float", 200.0,
     "Ceiling for the p95-derived hedge delay."),
    ("EASYDL_SERVE_ROUTER_HOLDDOWN_S", "float", 2.0,
     "Hold-down before an ejected replica is re-probed for rotation."),
    ("EASYDL_SERVE_ROUTER_EJECT_FAILS", "int", 3,
     "Consecutive transport failures (or hard sheds) that eject a "
     "replica from rotation."),
    ("EASYDL_SERVE_ROUTER_REFRESH_S", "float", 1.0,
     "Replica discovery refresh cadence (workdir serve/ registry scan)."),
    # -- production loop: feedback stream + rollout -----------------------
    ("EASYDL_FEEDBACK_SPOOL_BYTES", "int", 268_435_456,  # 256 MiB
     "Per-replica feedback spool byte bound; past it (after retiring "
     "trainer-consumed segments) new events DROP with a count — the "
     "spool never blocks or fails a serve request."),
    ("EASYDL_FEEDBACK_SEGMENT_BYTES", "int", 8_388_608,  # 8 MiB
     "Feedback spool segment roll size."),
    ("EASYDL_FEEDBACK_SYNC_S", "float", 0.2,
     "Feedback spool fsync cadence; 0 = every append, negative = never."),
    ("EASYDL_FEEDBACK_POLL_S", "float", 0.2,
     "Continuous-trainer poll cadence on an exhausted spool "
     "(block-with-timeout, never terminate)."),
    ("EASYDL_FEEDBACK_LABEL_HORIZON_S", "float", 60.0,
     "Delayed-label join horizon: a serve event unlabeled past it trains "
     "with the implicit negative label."),
    ("EASYDL_ROLLOUT_POLL_S", "float", 0.5,
     "Serve-side model-publication watcher poll cadence."),
    ("EASYDL_ROLLOUT_KEEP", "int", 4,
     "Committed model versions the publisher keeps on disk."),
    ("EASYDL_ROLLOUT_CANARY_FRACTION", "float", 0.1,
     "Session-hash fraction routed to the canary arm while one is "
     "active (sessions without an id always serve control)."),
    ("EASYDL_ROLLOUT_SALT", "str", "",
     "Session->arm hash salt; rotate to reshuffle the A/B population."),
    # -- retrieval: two-tower + ANN index ---------------------------------
    ("EASYDL_RETRIEVAL_USER_TABLE", "str", "tt_user",
     "PS table holding the user-tower context embeddings."),
    ("EASYDL_RETRIEVAL_ITEM_TABLE", "str", "tt_item",
     "PS table holding the item-tower embeddings; pushes to it are what "
     "the index builder tails into retrievability."),
    ("EASYDL_RETRIEVAL_K", "int", 10,
     "Default candidate count a Retrieve request gets when it asks for "
     "k<=0."),
    ("EASYDL_RETRIEVAL_NLIST", "int", 16,
     "ANN index bucket count (k-means centroids) once clustered."),
    ("EASYDL_RETRIEVAL_NPROBE", "int", 8,
     "Centroid buckets probed per query; >= nlist degenerates to exact "
     "brute force."),
    ("EASYDL_RETRIEVAL_POLL_S", "float", 0.05,
     "Index-builder WAL tail poll cadence on an exhausted log."),
    ("EASYDL_RETRIEVAL_CKPT_EVERY", "int", 8,
     "Applied incremental updates between index snapshot publications "
     "(snapshot first, cursor second — the exactly-once boundary)."),
    ("EASYDL_RETRIEVAL_FRESHNESS_SLO_S", "float", 5.0,
     "Push->retrievable freshness SLO the bench gates p99 against."),
    ("EASYDL_RETRIEVAL_TEMPERATURE", "float", 0.05,
     "In-batch sampled-softmax temperature for two-tower training."),
    ("EASYDL_RETRIEVAL_REBUILD_MIN_ROWS", "int", 64,
     "Rows before the flat index first clusters; below it brute force is "
     "exact and cheap."),
    # -- mesh-shape policy / MFU ------------------------------------------
    ("EASYDL_MESH_PIN", "str", "",
     "Operator override: pin the elastic mesh-shape policy to this shape "
     "key ('dp=8'); invalid-for-world pins fall back to the policy."),
    ("EASYDL_CHIP_PEAK_TFLOPS", "float", 0.0,
     "MFU denominator override: this chip's peak dense TFLOP/s (wins over "
     "the built-in device-kind table; unset+unknown chip = error)."),
    # -- storage / caches -------------------------------------------------
    ("EASYDL_COMPILE_CACHE", "str", "",
     "'off' disables the persistent XLA compile cache; its directory is "
     "JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache."),
    ("EASYDL_CHUNK_CACHE", "str", "",
     "Dataset chunk cache: 0/off disables, a path overrides the root."),
    ("EASYDL_GCS_ENDPOINT", "str", "https://storage.googleapis.com",
     "GCS base URL override (fake server / proxy)."),
    ("EASYDL_GCE_METADATA_URL", "str", "",
     "GCE metadata server override (tests, proxies)."),
    # -- SLOs / alerting (obs/slo.py, obs/alerts.py) ----------------------
    ("EASYDL_SLO_DIR", "str", "",
     "SLO spec directory the alert evaluator loads; '' = the repo's "
     "slos/."),
    ("EASYDL_ALERT_EVAL_INTERVAL_S", "float", 0.5,
     "Alert evaluator cadence: one fleet snapshot + one pure burn-rate "
     "decision per tick."),
    ("EASYDL_ALERT_LEDGER_SEGMENT_BYTES", "int", 4_194_304,  # 4 MiB
     "Alert-decision ledger (spool-framed JSONL) segment roll size."),
    ("EASYDL_ALERT_TTD_BUDGET_S", "float", 15.0,
     "Default time-to-detect budget a drill's expected alert must fire "
     "within (per-scenario expect.detect.ttd_budget_s overrides)."),
    ("EASYDL_ALERT_DRILL_RECORD", "bool", True,
     "Chaos harness records the alert timeline during every drill "
     "(detected_and_cleared evidence); off skips the recorder thread."),
    ("EASYDL_ALERT_SETTLE_S", "float", 12.0,
     "Max seconds teardown waits for a drill's expected alert to clear "
     "before stopping the recorder (the clear half of "
     "detected_and_cleared needs one clean long window)."),
    ("EASYDL_SCRAPE_POOL", "int", 8,
     "Bounded worker pool for concurrent fleet scrapes "
     "(obs.scrape.scrape_fleet)."),
    # -- chaos / harness child markers ------------------------------------
    ("EASYDL_CHAOS_SPEC", "str", "",
     "Armed chaos scenario spec path; unset = every hook is one dict "
     "lookup."),
    ("EASYDL_CHAOS_CHILD", "str", "",
     "Marks the re-exec'd forced-CPU chaos_run child ('1')."),
    ("EASYDL_RECOVERY_CHILD", "str", "",
     "Marks the re-exec'd measure_recovery child ('1')."),
)


@dataclass(frozen=True)
class Knob:
    name: str
    type: str
    default: object
    help: str


KNOBS: Dict[str, Knob] = {d[0]: Knob(*d) for d in KNOB_DECLS}

_UNSET = object()


def _declared(name: str) -> Knob:
    k = KNOBS.get(name)
    if k is not None:
        return k
    for fam, kf in KNOBS.items():  # family declarations: trailing *
        if fam.endswith("*") and name.startswith(fam[:-1]):
            return kf
    raise KeyError(
        f"{name} is not declared in easydl_tpu.utils.env.KNOB_DECLS — "
        "declare it (name, type, default, help) and add it to the "
        "docs/operations.md knob table")


def knob_raw(name: str, env: Optional[Mapping[str, str]] = None,
             ) -> Optional[str]:
    """The declared-but-untyped read: raw value or None when unset. For
    save/restore idioms and presence checks; typed reads use knob_*."""
    _declared(name)
    return (env if env is not None else os.environ).get(name)


def _resolve(name: str, default, env) -> Optional[str]:
    knob = _declared(name)
    v = (env if env is not None else os.environ).get(name)
    if v is not None:
        return v
    d = knob.default if default is _UNSET else default
    if d is None:
        raise KeyError(f"required knob {name} is not set")
    return d


def knob_str(name: str, default=_UNSET,
             env: Optional[Mapping[str, str]] = None) -> str:
    return str(_resolve(name, default, env))


def knob_int(name: str, default=_UNSET,
             env: Optional[Mapping[str, str]] = None) -> int:
    return int(_resolve(name, default, env))


def knob_float(name: str, default=_UNSET,
               env: Optional[Mapping[str, str]] = None) -> float:
    return float(_resolve(name, default, env))


def knob_bool(name: str, default=_UNSET,
              env: Optional[Mapping[str, str]] = None) -> bool:
    v = _resolve(name, default, env)
    if isinstance(v, bool):
        return v
    return v not in ("", "0", "false", "False")


def env_flag(name: str, default: bool) -> bool:
    """Boolean EASYDL_* knob convention: unset → ``default``; ``"0"``,
    ``"false"``/``"False"`` and empty mean off; anything else means on.
    (Deliberately lenient about undeclared names — tests mint throwaway
    flags; the knob-registry lint still checks literal in-tree uses.)"""
    v = os.environ.get(name)
    if v is None:
        return default
    return v not in ("", "0", "false", "False")


def obs_port_from_env(component: str, default: int = 0):
    """Resolve a service's metrics-exporter port from the environment.

    Precedence: ``EASYDL_METRICS_PORT_<COMPONENT>`` (component upper-cased,
    non-alnum → ``_``) > ``EASYDL_METRICS_PORT`` > ``default`` (0 = pick a
    free port). ``off``/``disabled``/negative disables the exporter —
    returns None. Unparseable values fall back to the default rather than
    killing the service: observability must never be load-bearing."""
    key = "EASYDL_METRICS_PORT_" + "".join(
        c if c.isalnum() else "_" for c in component
    ).upper()
    raw = os.environ.get(key) or os.environ.get("EASYDL_METRICS_PORT")
    if raw is None:
        return default
    raw = raw.strip().lower()
    if raw in ("off", "disabled", "none", "false"):
        return None
    try:
        port = int(raw)
    except ValueError:
        return default
    if port < 0:
        return None
    if port > 65535:  # a typo'd port must not take the service down
        return default
    return port


def cpu_subprocess_env(
    n_devices: int, base: Optional[Mapping[str, str]] = None
) -> Dict[str, str]:
    """Environment for a subprocess that must initialise JAX on a forced
    ``n_devices``-device CPU platform — the single authoritative copy of the
    recipe used by the elastic agent's worker spawns and the driver's
    ``dryrun_multichip`` bootstrap.
    """
    env = dict(base if base is not None else os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    )
    return env


def default_platform() -> str:
    """The JAX platform a process runs on when nobody states one: the first
    entry of ``JAX_PLATFORMS``, else ``"tpu"`` (what jax picks on the hosts
    this framework is for). Read from the environment, never from jax: a
    parent that asks jax takes the host's accelerator for itself."""
    return (os.environ.get("JAX_PLATFORMS") or "tpu").split(",")[0].strip()


def rerun_on_cpu_mesh(script: str, child_knob: str,
                      n_devices: int = 8) -> None:
    """Self-bootstrap of the scripts whose scenarios need a multi-device CPU
    platform (measure_recovery, chaos_run, bench_failover): unless this
    process is the marked child already, or ``JAX_PLATFORMS`` already says
    cpu, re-run ``script`` with its arguments in a forced-CPU child and exit
    with the child's code."""
    if os.environ.get(child_knob) == "1" or default_platform() == "cpu":
        return
    import subprocess
    import sys

    env = cpu_subprocess_env(n_devices)
    env[child_knob] = "1"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # No wall-clock cap: each scenario bounds itself; an outer timeout would
    # SIGKILL the child mid-scenario and lose the in-flight verdict.
    raise SystemExit(subprocess.run(
        [sys.executable, os.path.abspath(script)] + sys.argv[1:],
        env=env, cwd=REPO_ROOT,
    ).returncode)


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
#: does not say: one fixed, git-ignored directory of the checkout. The path
#: is part of what makes a cache entry findable — a directory built from a
#: workdir, a temporary name, a pid or the time never hits twice.
COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for this process; returns
    its directory, or None when ``EASYDL_COMPILE_CACHE=off`` disabled it.

    The one resolver every entry point that compiles uses (zoo runner,
    elastic worker, chip_smoke's children). Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it, and no
    directory is set in code; otherwise the cache is
    :data:`COMPILE_CACHE_DIR`. Thresholds go to 0 so test-scale compiles
    are cached too. Call after importing jax, before the first compile."""
    import jax

    if knob_str("EASYDL_COMPILE_CACHE").strip().lower() in (
            "off", "0", "none", "disabled"):
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # A Mosaic kernel is serialized into its program WITH its MLIR
    # locations, and by default a location holds the Python call stack that
    # traced it (ten frames). The same train step traced after an init (a
    # fresh run, an elastic generation 1) and after a restore (a resumed
    # run, every later generation) then differs in bytes the cache keys on,
    # and the restart the cache exists for compiles cold (seen on the chip:
    # 14.5 s twice). One frame per location does not depend on the caller.
    # (Not ``jax_include_full_tracebacks_in_locations=False``, which gives
    # one frame too: jax then writes locations in a form from which XLA does
    # not compose an operation's name stack through calls and loops — the
    # compiled step's op_name was the bare primitive, and the passes and
    # scopes the device trace is read by were gone.)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    # The key leaves the locations of a program's operations out unless told
    # otherwise, and the name stack (named scopes, module names, a Pallas
    # kernel's ``name=``) lives in them: a program found in the cache then
    # wears the names of whichever version was compiled first. The device
    # trace is read by those names, so they belong to the key. The price: an
    # edit that moves a traced line compiles cold once, as a checkout at a
    # new path already does.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return cache_dir


def join_rank_processes(procs, timeout: float = 900.0, poll_s: float = 0.25):
    """Join coordinated rank subprocesses (stdout/stderr PIPEd), fail-fast.

    A crashed rank leaves its peers blocked in a collective; waiting out the
    full timeout hides the root cause for minutes and then discards the
    failing rank's stderr. Poll instead: the moment any rank exits non-zero
    (or the deadline passes) kill the stragglers, then harvest every rank's
    output. Pipes are drained CONCURRENTLY by reader threads — draining
    only after exit would deadlock any child whose chatter exceeds the OS
    pipe buffer (it blocks in write(), never exits, and a passing run turns
    into a full-timeout kill). Returns ``[(returncode, stdout, stderr)]``
    in rank order — killed stragglers report negative returncodes; the
    caller should report the *non-signal* failures first.
    """
    import threading
    import time

    def drain(stream, sink):
        if stream is None:
            return
        while True:  # empty-chunk EOF test works for text AND binary pipes
            chunk = stream.read(8192)
            if not chunk:
                return
            sink.append(chunk)

    buffers = []
    readers = []
    for p in procs:
        out_buf, err_buf = [], []
        buffers.append((out_buf, err_buf))
        for stream, sink in ((p.stdout, out_buf), (p.stderr, err_buf)):
            t = threading.Thread(target=drain, args=(stream, sink),
                                 daemon=True)
            t.start()
            readers.append(t)

    deadline = time.monotonic() + timeout
    try:
        while True:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes):
                break
            if any(c not in (None, 0) for c in codes):
                break  # a rank failed: don't wait for the blocked peers
            if time.monotonic() > deadline:
                break
            time.sleep(poll_s)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p in procs:
        p.wait()
    for t in readers:
        t.join(timeout=10.0)
    def joined(buf):
        return (b"" if buf and isinstance(buf[0], bytes) else "").join(buf)

    return [
        (p.returncode, joined(out_buf), joined(err_buf))
        for p, (out_buf, err_buf) in zip(procs, buffers)
    ]


def run_cpu_rank_fleet(argvs, n_local_devices: int, timeout: float = 900.0,
                       cwd=None):
    """Spawn one forced-CPU jax subprocess per argv (a coordinated rank
    fleet), join with fail-fast, and surface failures.

    The single authoritative copy of the spawn/report idiom shared by
    ``dryrun_multichip``'s multi-process leg and the measurement scripts:
    per-rank ``cpu_subprocess_env`` + repo PYTHONPATH, concurrent pipe
    drains via :func:`join_rank_processes`, stdouts replayed in rank order,
    and failures reported with *real* (non-signal) exits first — a killed
    straggler's -9 must not mask the rank whose stderr holds the root
    cause. Raises RuntimeError naming the failing rank; returns the list
    of rank stdouts on success."""
    import os
    import subprocess
    import sys

    root = cwd or os.getcwd()
    procs = []
    for argv in argvs:
        env = cpu_subprocess_env(n_local_devices)
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            argv, env=env, cwd=root,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results = join_rank_processes(procs, timeout=timeout)
    for rc, out, err in results:
        sys.stdout.write(out)
    for rank, (rc, out, err) in sorted(
            enumerate(results), key=lambda kv: kv[1][0] >= 0, reverse=True):
        if rc != 0:
            sys.stderr.write(err)
            raise RuntimeError(f"rank {rank} failed rc={rc}")
    return [out for _, out, _ in results]

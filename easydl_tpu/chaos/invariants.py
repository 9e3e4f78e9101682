"""Recovery-invariant checking: the assertion half of a chaos drill.

A drill that merely *survives* proves little — the point is that after the
injected faults the job provably recovered CORRECTLY. This module folds the
artifacts every simulated-distributed run already produces — per-agent
``metrics-*.jsonl``, the master's ``events.jsonl``, the final rendezvous
status, and the PR-1 obs registry/scrape counters — into named invariant
verdicts:

- ``reached_target_step`` — the job got to its goal (DONE marker or a step
  record at/after the target);
- ``generation_monotonic`` — the master's generation never moved backwards
  across the whole event log (a regressed generation means split-brain);
- ``steps_lost_bounded`` — across every generation switch, the work thrown
  away is at most the declared bound (≤ ckpt_interval for plain kills; a
  corrupted-checkpoint fallback legitimately pays one more interval, so the
  scenario declares its own bound);
- ``membership_converged`` — the final world is the planned one (member
  count AND the world size the workers actually trained at);
- ``no_directive_ping_pong`` — the master reshaped at most the expected
  number of times: flapping (kill → rejoin → kill ...) shows up as excess
  ``draining`` transitions even when the job eventually finishes;
- ``no_spurious_reshape_after_failover`` — after a master restart restored
  the membership journal (the WAL's ``failover`` record), the generation
  advanced at most the declared number of times: a failover over a healthy
  fleet must cost ZERO reshapes;
- ``training_progress_during_outage`` — step records were written INSIDE
  every control-plane outage window: the data plane kept training while the
  master was dead;
- ``ps_zero_loss_bit_identical`` — after a PS-shard crash + rescue, every
  table's saved state (embedding AND optimizer rows, all shards merged,
  id-sorted) digest-matches a fault-free in-process replay of the exact
  same push stream: the recovery lost NOTHING, not "recovered to the last
  snapshot";
- ``ps_wal_replayed`` — the rescue actually consumed WAL records (a
  zero-loss pass with an empty log would be vacuous: it would only prove
  the kill landed before any post-snapshot push);
- ``ps_zombie_fenced`` — the SIGSTOP-resumed predecessor rejected a push
  stamped with its own superseded epoch AND wrote zero WAL bytes past the
  rescuer's replay caps: a zombie writer can never diverge the table;
- ``ps_reshard_completed`` — every online-reshard migration the drill
  launched committed its new routing generation with no errors, actually
  moved rows into the destination set (``min_rows_migrated``), and
  replayed at least ``min_reshard_replays`` mid-migration WAL tail
  pushes — a "pass" where the migration never ran, or ran against a
  silent tier, is refused (same no-vacuous-pass stance as
  ``ps_wal_replayed``);
- ``ps_tier_spilled`` — a drill billed as beyond-RAM really ran beyond the
  hot arena: the pods' tier counters show at least ``min_tier_cold_rows``
  rows resident in the mmap cold tier, at least one demotion, and at least
  one access served from the cold tier — a "pass" where the table fit in
  RAM the whole time would prove nothing about spilled-state recovery;
- ``straggler_mitigated`` — the master's skew detector actually evicted
  the declared straggler (``straggler_evicted`` WAL record), the final
  membership excludes it, and — when the scenario declares
  ``evict_budget_s`` — the eviction landed within budget of the armed
  straggler window's start;
- ``holddown_quiet`` — the anti-ping-pong half: after each eviction, NO
  further reshape inside the detector's hold-down window (beyond the
  mitigation reshape itself); vacuous-pass refused when no eviction
  happened;
- ``proactive_drain_before_kill`` — the preemption race: the noticed
  member's own ``quiesce_exit`` timeline record (checkpoint committed,
  worker exited) precedes the harness' kill mark, and the kill found no
  live worker — reactive crash-recovery after the kill fails the drill;
- ``faults_observed`` (cross-check) — the obs counters saw at least the
  expected number of injected faults, so a "pass" can't come from a drill
  that silently injected nothing;
- ``detected_and_cleared`` — the drill's alerting witness (the harness'
  AlertRecorder running the real ``slos/*.yaml`` policy) saw the
  injected fault's expected alert fire within the per-scenario TTD
  budget AND clear after recovery, and the recorded alert-decision log
  re-derives byte-identically offline; a drill that ran without the
  witness fails, never skips;
- ``no_false_pages`` — the anti-vacuous negative control: a fault-free
  run must fire ZERO page-severity alerts while the witness provably
  ran.

Expectations are a plain dict so scenarios stay declarative::

    expect = {"target_step": 24, "max_steps_lost": 4, "final_workers": 2,
              "final_world_devices": 2, "max_reshapes": 2, "min_faults": 1}
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Mapping, Optional

from easydl_tpu.elastic.goodput import wasted_steps


def read_metrics(workdir: str) -> List[Dict[str, Any]]:
    """All agents' step records, merged (unsorted)."""
    out: List[Dict[str, Any]] = []
    try:
        names = os.listdir(workdir)
    except OSError:
        return out
    for name in sorted(names):
        if not (name.startswith("metrics-") and name.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(workdir, name)) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            continue  # torn tail from a killed worker
        except OSError:
            continue
    return out


def read_metrics_by_agent(workdir: str) -> Dict[str, List[Dict[str, Any]]]:
    """Step records keyed by the agent whose file they came from (the
    records themselves carry no agent id — the filename does)."""
    out: Dict[str, List[Dict[str, Any]]] = {}
    try:
        names = os.listdir(workdir)
    except OSError:
        return out
    for name in sorted(names):
        if not (name.startswith("metrics-") and name.endswith(".jsonl")):
            continue
        agent = name[len("metrics-"):-len(".jsonl")]
        records: List[Dict[str, Any]] = []
        try:
            with open(os.path.join(workdir, name)) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        try:
                            records.append(json.loads(line))
                        except ValueError:
                            continue
        except OSError:
            continue
        out[agent] = records
    return out


def read_timeline(workdir: str, agent: str) -> List[Dict[str, Any]]:
    """One agent's phase-boundary timeline records (timeline.py JSONL)."""
    out: List[Dict[str, Any]] = []
    try:
        with open(os.path.join(workdir, f"timeline-{agent}.jsonl")) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue
    except OSError:
        pass
    return out


def read_events(workdir: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    try:
        with open(os.path.join(workdir, "events.jsonl")) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue
    except OSError:
        pass
    return out


def holddown_violations(
    evictions: List[Mapping[str, Any]],
    reshapes: List[Mapping[str, Any]],
) -> List[Dict[str, Any]]:
    """ONE copy of the hold-down rule, shared by the live drill checker
    and the offline simulator (sim/invariants.py) so the same-named
    invariant can never drift between the two: inside each eviction's
    hold-down window the ONLY permitted reshape is the mitigation itself
    — the first ``reason == "straggler"`` record — and anything else
    (matched by WAL attributes, not a timing fudge) is flapping."""
    out: List[Dict[str, Any]] = []
    for ev in evictions:
        te = float(ev.get("t", 0.0))
        h = float(ev.get("holddown_s", 0.0))
        inside = [r for r in reshapes
                  if te <= float(r.get("t", 0.0)) <= te + h]
        mitigation_seen = False
        flaps = []
        for r in inside:
            if not mitigation_seen and str(r.get("reason")) == "straggler":
                mitigation_seen = True
                continue
            flaps.append(dict(r))
        if flaps:
            out.append({"eviction": dict(ev), "reshapes": flaps})
    return out


def drain_race(drain_ts: List[float], kill_t: float,
               worker_alive: bool) -> Dict[str, Any]:
    """ONE copy of the preemption-race rule (live + sim): the drain wins
    iff a drain completion precedes the kill AND the kill found no live
    worker."""
    drain_t = max((t for t in drain_ts if t < kill_t), default=None)
    won = drain_t is not None and not worker_alive
    return {
        "kill_t": kill_t,
        "drain_t": drain_t,
        "worker_alive_at_kill": bool(worker_alive),
        "margin_s": (round(kill_t - drain_t, 6)
                     if drain_t is not None else None),
        "won": won,
    }


def _steps_by_generation(metrics: List[Dict[str, Any]]) -> Dict[int, List[int]]:
    by_gen: Dict[int, List[int]] = {}
    for r in metrics:
        try:
            by_gen.setdefault(int(r["generation"]), []).append(int(r["step"]))
        except (KeyError, TypeError, ValueError):
            continue
    return by_gen


def check_scenario(
    workdir: str,
    expect: Mapping[str, Any],
    status: Optional[Mapping[str, Any]] = None,
    fault_counts: Optional[Mapping[str, float]] = None,
    outages: Optional[List[Mapping[str, float]]] = None,
    kills: Optional[List[Mapping[str, Any]]] = None,
) -> Dict[str, Any]:
    """Run every applicable invariant; returns::

        {"passed": bool, "checks": {name: {"ok": bool, ...evidence...}}}

    ``status`` is the master's final ``status()`` snapshot (captured before
    teardown); ``fault_counts`` the injected-fault counters
    (injectors.injected_fault_counts or a merged scrape); ``outages`` the
    harness-recorded control-plane outage windows
    (``[{"t_down": wall, "t_up": wall}]``, ``t_up`` absent when the master
    never came back); ``kills`` the harness' worker_kill marks
    (``{"t": wall, "agent", "worker_alive"}``) — the preempt-race
    evidence."""
    metrics = read_metrics(workdir)
    events = read_events(workdir)
    by_gen = _steps_by_generation(metrics)
    checks: Dict[str, Dict[str, Any]] = {}

    # -------------------------------------------------- reached_target_step
    target = expect.get("target_step")
    if target is not None:
        max_step = max((max(v) for v in by_gen.values()), default=0)
        done = os.path.exists(os.path.join(workdir, "DONE"))
        checks["reached_target_step"] = {
            "ok": done or max_step >= int(target),
            "target": int(target), "max_step": max_step, "done_marker": done,
        }

    # -------------------------------------------------- generation_monotonic
    gens = [int(e["generation"]) for e in events
            if e.get("kind") == "phase" and "generation" in e]
    regressions = [
        (a, b) for a, b in zip(gens, gens[1:]) if b < a
    ]
    checks["generation_monotonic"] = {
        "ok": not regressions,
        "generations_seen": gens,
        "regressions": regressions,
    }

    # ---------------------------------------------------- steps_lost_bounded
    bound = expect.get("max_steps_lost")
    if bound is not None:
        ordered = sorted(g for g in by_gen if by_gen[g])
        losses = []
        for prev, nxt in zip(ordered, ordered[1:]):
            # Time-aware boundary: an evicted-but-alive agent's zombie
            # worker keeps recording steps at the OLD generation after the
            # new one already started (the heartbeat-loss drill); counting
            # those post-switch records as "work lost at the switch" would
            # inflate the loss. The work at risk is what the old generation
            # had recorded when the new one's first step landed.
            t_first_next = min(
                float(r.get("t", 0.0)) for r in metrics
                if int(r.get("generation", -1)) == nxt
            )
            pre = [int(r["step"]) for r in metrics
                   if int(r.get("generation", -1)) == prev
                   and float(r.get("t", 0.0)) <= t_first_next]
            # the job's own reckoning (elastic/goodput.py): the steps run
            # above the one the next generation resumed from
            lost = len(wasted_steps(pre or by_gen[prev],
                                    min(by_gen[nxt]) - 1))
            losses.append({"from_gen": prev, "to_gen": nxt,
                           "steps_lost": lost})
        worst = max((l["steps_lost"] for l in losses), default=0)
        checks["steps_lost_bounded"] = {
            "ok": worst <= int(bound),
            "bound": int(bound), "worst": worst, "transitions": losses,
        }

    # --------------------------------------------------- membership_converged
    want_workers = expect.get("final_workers")
    want_devices = expect.get("final_world_devices")
    if want_workers is not None or want_devices is not None:
        members = list((status or {}).get("members", []))
        final_gen = max(by_gen, default=-1)
        final_worlds = sorted({
            int(r.get("world_size", 0)) for r in metrics
            if int(r.get("generation", -1)) == final_gen
        })
        ok = True
        if want_workers is not None:
            ok = ok and len(members) == int(want_workers)
        if want_devices is not None:
            ok = ok and final_worlds == [int(want_devices)]
        checks["membership_converged"] = {
            "ok": ok,
            "final_members": members,
            "want_workers": want_workers,
            "final_generation": final_gen,
            "final_world_sizes": final_worlds,
            "want_world_devices": want_devices,
        }

    # ------------------------------------------------- no_directive_ping_pong
    max_reshapes = expect.get("max_reshapes")
    if max_reshapes is not None:
        # The master's event log samples phases every tick — a drain that
        # forms the next generation within one tick never lands in it, so
        # the generation counter (one increment per formed generation,
        # initial formation = 1) is the authoritative reshape count; the
        # drain transitions are kept as corroborating evidence.
        drains = [e for e in events
                  if e.get("kind") == "phase" and e.get("phase") == "draining"]
        gen_final = int((status or {}).get("generation", 0))
        reshapes = max(len(drains), gen_final - 1)
        checks["no_directive_ping_pong"] = {
            "ok": reshapes <= int(max_reshapes),
            "reshapes": reshapes,
            "drain_transitions": len(drains),
            "final_generation": gen_final,
            "max_reshapes": int(max_reshapes),
        }

    # --------------------------------------------------- recovery_happened
    min_gen = expect.get("min_final_generation")
    if min_gen is not None:
        gen_final = int((status or {}).get("generation", 0))
        checks["recovery_happened"] = {
            "ok": gen_final >= int(min_gen),
            "final_generation": gen_final,
            "min_final_generation": int(min_gen),
        }

    # ----------------------------------- no_spurious_reshape_after_failover
    max_after = expect.get("max_reshapes_after_failover")
    if max_after is not None:
        failovers = [e for e in events if e.get("kind") == "failover"]
        if not failovers:
            # The drill PROMISED a failover; a run where the restarted
            # master never restored the journal must not pass vacuously.
            checks["no_spurious_reshape_after_failover"] = {
                "ok": False,
                "reason": "no failover event in the WAL (journal not "
                          "restored?)",
                "max_reshapes_after_failover": int(max_after),
            }
        else:
            last = failovers[-1]
            gen_at_failover = int(last.get("generation", 0))
            gen_final = int((status or {}).get("generation", gen_at_failover))
            reshapes_after = max(0, gen_final - gen_at_failover)
            checks["no_spurious_reshape_after_failover"] = {
                "ok": reshapes_after <= int(max_after),
                "failovers": len(failovers),
                "generation_at_failover": gen_at_failover,
                "final_generation": gen_final,
                "reshapes_after_failover": reshapes_after,
                "max_reshapes_after_failover": int(max_after),
            }

    # --------------------------------------- training_progress_during_outage
    min_outage_steps = expect.get("min_steps_during_outage")
    if min_outage_steps is not None:
        windows = [
            (float(o["t_down"]), float(o.get("t_up", float("inf"))))
            for o in (outages or [])
        ]
        if not windows:
            checks["training_progress_during_outage"] = {
                "ok": False,
                "reason": "no control-plane outage recorded by the harness",
                "min_steps_during_outage": int(min_outage_steps),
            }
        else:
            # Progress is judged PER AGENT (max−min within one worker's
            # records), then the best agent per window: pooling all agents'
            # records would read the step SPREAD between two stalled
            # workers as progress.
            by_agent = read_metrics_by_agent(workdir)
            evidence = []
            ok = True
            for t_down, t_up in windows:
                per_agent = {}
                for agent, records in by_agent.items():
                    steps = [
                        int(r["step"]) for r in records
                        if t_down <= float(r.get("t", 0.0)) <= t_up
                        and "step" in r
                    ]
                    if steps:
                        per_agent[agent] = {
                            "records": len(steps),
                            "progress": max(steps) - min(steps),
                        }
                progress = max(
                    (v["progress"] for v in per_agent.values()), default=0)
                evidence.append({
                    "t_down": t_down,
                    "t_up": None if t_up == float("inf") else t_up,
                    "per_agent": per_agent,
                    "step_progress": progress,
                })
                ok = ok and progress >= int(min_outage_steps)
            checks["training_progress_during_outage"] = {
                "ok": ok,
                "windows": evidence,
                "min_steps_during_outage": int(min_outage_steps),
            }

    # ------------------------------------------------- straggler mitigation
    evicted = expect.get("straggler_evicted")
    if evicted is not None:
        evict_events = [e for e in events
                        if e.get("kind") == "straggler_evicted"
                        and e.get("agent") == evicted]
        members = list((status or {}).get("members", []))
        if not evict_events:
            # The drill PROMISED an eviction; a run where the detector
            # never fired must not pass on the reshape bound alone.
            checks["straggler_mitigated"] = {
                "ok": False,
                "reason": "no straggler_evicted event in the WAL "
                          "(detector never fired?)",
                "agent": evicted,
            }
        else:
            ev = evict_events[0]
            ok = evicted not in members
            budget = expect.get("evict_budget_s")
            latency = None
            if budget is not None:
                # Onset = the armed schedule's straggler window start
                # (t0 + start_s), read from the plan the harness wrote.
                onset = _straggler_onset(workdir, evicted)
                if onset is None:
                    ok = False
                else:
                    latency = round(float(ev.get("t", 0.0)) - onset, 3)
                    ok = ok and 0 <= latency <= float(budget)
            checks["straggler_mitigated"] = {
                "ok": ok,
                "agent": evicted,
                "evictions": len(evict_events),
                "final_members": members,
                "latency_s": latency,
                "evict_budget_s": budget,
            }

    if expect.get("holddown_quiet"):
        evict_events = [e for e in events
                        if e.get("kind") == "straggler_evicted"]
        reshape_events = [e for e in events if e.get("kind") == "reshape"]
        if not evict_events:
            checks["holddown_quiet"] = {
                "ok": False,
                "reason": "no eviction in the WAL — the anti-ping-pong "
                          "window was never exercised (vacuous)",
            }
        else:
            violations = holddown_violations(evict_events, reshape_events)
            checks["holddown_quiet"] = {
                "ok": not violations,
                "evictions": len(evict_events),
                "violations": violations,
            }

    # -------------------------------------------------- proactive drain race
    race_agent = expect.get("proactive_drain")
    if race_agent:
        marks = [k for k in (kills or [])
                 if str(k.get("agent", "")) == str(race_agent)]
        if not marks:
            checks["proactive_drain_before_kill"] = {
                "ok": False,
                "reason": "no worker_kill mark recorded for the noticed "
                          "agent — the race was never run (vacuous)",
                "agent": race_agent,
            }
        else:
            tl = read_timeline(workdir, str(race_agent))
            quiesce_exits = [float(r.get("t", 0.0)) for r in tl
                             if r.get("phase") == "quiesce_exit"]
            evidence = [
                drain_race(quiesce_exits, float(k.get("t", 0.0)),
                           bool(k.get("worker_alive")))
                for k in marks
            ]
            checks["proactive_drain_before_kill"] = {
                "ok": all(e["won"] for e in evidence),
                "agent": race_agent, "races": evidence,
            }

    # ------------------------------------------------------- ps zero loss
    if expect.get("ps_zero_loss"):
        evidence: Dict[str, Any] = {}
        try:
            with open(os.path.join(workdir, "ps-zero-loss.json")) as f:
                evidence = json.load(f)
        except (OSError, ValueError):
            pass
        if not evidence:
            # The drill PROMISED digest evidence; a storm that crashed
            # before writing it must not pass vacuously.
            checks["ps_zero_loss_bit_identical"] = {
                "ok": False,
                "reason": "no ps-zero-loss.json evidence in the workdir",
            }
        else:
            checks["ps_zero_loss_bit_identical"] = {
                "ok": bool(evidence.get("digests_match")),
                "live_digests": evidence.get("live_digests", {}),
                "reference_digests": evidence.get("reference_digests", {}),
            }
            min_replays = expect.get("min_wal_replays")
            if min_replays is not None:
                counters = evidence.get("counters", {}) or {}
                replayed = float(counters.get("wal_replayed_records", 0.0))
                checks["ps_wal_replayed"] = {
                    "ok": replayed >= float(min_replays),
                    "wal_replayed_records": replayed,
                    "min_wal_replays": float(min_replays),
                    "counters": counters,
                }
            min_migrations = expect.get("min_reshard_migrations")
            if min_migrations is not None:
                resh = evidence.get("reshard") or {}
                migrations = resh.get("migrations", []) or []
                errors = resh.get("errors", []) or []
                committed = [m for m in migrations
                             if m.get("committed_routing")]
                rows = sum(int(m.get("rows_migrated", 0))
                           for m in committed)
                tail = sum(int(m.get("tail_pushes_replayed", 0))
                           for m in committed)
                min_rows = int(expect.get("min_rows_migrated", 1))
                min_tail = int(expect.get("min_reshard_replays", 1))
                checks["ps_reshard_completed"] = {
                    "ok": (not errors
                           and len(committed) >= int(min_migrations)
                           and rows >= min_rows and tail >= min_tail),
                    "migrations_committed": len(committed),
                    "min_reshard_migrations": int(min_migrations),
                    "rows_migrated": rows,
                    "min_rows_migrated": min_rows,
                    "tail_pushes_replayed": tail,
                    "min_reshard_replays": min_tail,
                    "errors": errors,
                    "committed_routing": [m.get("committed_routing")
                                          for m in committed],
                }
            min_cold = expect.get("min_tier_cold_rows")
            if min_cold is not None:
                counters = evidence.get("counters", {}) or {}
                cold_rows = float(counters.get("tier_cold_rows", 0.0))
                demotions = float(counters.get("tier_demotions", 0.0))
                cold_hits = float(counters.get("tier_cold_hits", 0.0))
                checks["ps_tier_spilled"] = {
                    "ok": (cold_rows >= float(min_cold)
                           and demotions >= 1.0 and cold_hits >= 1.0),
                    "tier_cold_rows": cold_rows,
                    "min_tier_cold_rows": float(min_cold),
                    "tier_demotions": demotions,
                    "tier_cold_hits": cold_hits,
                    "tier_hot_rows": float(
                        counters.get("tier_hot_rows", 0.0)),
                    "tier_promotions": float(
                        counters.get("tier_promotions", 0.0)),
                }
            if (expect.get("serve_no_hard_failures")
                    or expect.get("serve_no_stale_reads")
                    or expect.get("min_serve_requests") is not None):
                sv = evidence.get("serve") or {}
                if not sv:
                    checks["serve_healthy"] = {
                        "ok": False,
                        "reason": "no serve evidence recorded (serving "
                                  "replica never ran?)",
                    }
                else:
                    stale = sv.get("stale_check") or {}
                    cache = sv.get("cache") or {}
                    min_req = int(expect.get("min_serve_requests", 1))
                    min_hits = int(expect.get("min_serve_cache_hits", 0))
                    ok = not sv.get("errors")
                    ok = ok and int(sv.get("requests", 0)) >= min_req
                    if expect.get("serve_no_hard_failures"):
                        ok = ok and int(sv.get("hard_failures", -1)) == 0
                    if expect.get("serve_no_stale_reads"):
                        # Anti-vacuous both ways: the check must have
                        # examined at least one id AND found zero stale.
                        ok = (ok and int(stale.get("ids_checked", 0)) > 0
                              and int(stale.get("stale_rows", -1)) == 0)
                    if min_hits:
                        # A run the cache never served would prove
                        # nothing about invalidation under the split.
                        ok = ok and float(cache.get("hits", 0)) >= min_hits
                    checks["serve_healthy"] = {
                        "ok": ok,
                        "requests": sv.get("requests"),
                        "ok_requests": sv.get("ok"),
                        "shed": sv.get("shed"),
                        "hard_failures": sv.get("hard_failures"),
                        "failure_samples": sv.get("failure_samples"),
                        "stale_check": stale,
                        "cache_hits": cache.get("hits"),
                        "cache_hit_ratio": cache.get("hit_ratio"),
                        "errors": sv.get("errors"),
                        "min_serve_requests": min_req,
                    }
            if expect.get("zombie_fenced"):
                z = evidence.get("zombie") or {}
                if not z:
                    checks["ps_zombie_fenced"] = {
                        "ok": False,
                        "reason": "no zombie evidence recorded (SIGSTOP "
                                  "fault never executed?)",
                    }
                else:
                    rejected = bool(z.get("probe_rejected_stale_epoch"))
                    excess = int(z.get("excess_wal_bytes", -1))
                    checks["ps_zombie_fenced"] = {
                        # Both halves: the direct old-epoch probe was
                        # turned away, AND the zombie's WAL shows no
                        # append past what the rescuer replayed (no
                        # stale-epoch push was ever APPLIED — an applied
                        # push always logs first).
                        "ok": rejected and excess == 0
                        and bool(z.get("replay_caps_found")),
                        "probe_rejected_stale_epoch": rejected,
                        "probe_message": z.get("probe_message",
                                               z.get("probe_error", "")),
                        "excess_wal_bytes": excess,
                        "replay_caps_found": bool(
                            z.get("replay_caps_found")),
                        "zombie": {k: z.get(k) for k in
                                   ("shard", "pod", "epoch", "address")},
                    }

    # ---------------------------------------------------- serve fleet (r19)
    if expect.get("fleet_resilient"):
        ev: Dict[str, Any] = {}
        try:
            with open(os.path.join(workdir, "fleet-evidence.json")) as f:
                ev = json.load(f)
        except (OSError, ValueError):
            pass
        if not ev:
            checks["serve_fleet_resilient"] = {
                "ok": False,
                "reason": "no fleet-evidence.json in the workdir (drill "
                          "crashed before writing evidence)",
            }
        else:
            router = ev.get("router") or {}
            stale = ev.get("stale_check") or {}
            min_req = int(expect.get("min_fleet_requests", 1))
            max_p99 = float(expect.get("max_p99_s", 5.0))
            hedges = int(router.get("hedges_fired", 0))
            rescued = (int(router.get("hedges_won", 0))
                       + int(router.get("hedges_rescued", 0)))
            p99_post = float(ev.get("p99_post_kill_s", -1.0))
            # Anti-vacuous: a pass REQUIRES a real kill, a real ejection,
            # hedges that fired AND won/rescued, served traffic past the
            # floor, post-kill latency evidence, at least one shm pull
            # observed, and a non-empty bit-exact stale check spanning
            # acked pushes. Zero-hedge or zero-ejection runs fail — they
            # prove the flood missed the fault, not that the fleet rode
            # it out.
            ok = (int(ev.get("requests", 0)) >= min_req
                  and int(ev.get("hard_failures", -1)) == 0
                  and bool(ev.get("kill"))
                  and int(router.get("ejections", 0)) >= 1
                  and hedges >= 1
                  and rescued >= 1
                  and int(stale.get("scores_checked", 0)) > 0
                  and int(stale.get("mismatches", -1)) == 0
                  and int(stale.get("push_phases", 0)) >= 1
                  and 0.0 < p99_post <= max_p99
                  and float(ev.get("shm_client_pulls", 0.0)) >= 1.0)
            checks["serve_fleet_resilient"] = {
                "ok": ok,
                "requests": ev.get("requests"),
                "ok_requests": ev.get("ok"),
                "shed": ev.get("shed"),
                "hard_failures": ev.get("hard_failures"),
                "failure_samples": ev.get("failure_samples"),
                "kill": ev.get("kill"),
                "ejections": router.get("ejections"),
                "readmissions": router.get("readmissions"),
                "hedges_fired": hedges,
                "hedges_won": router.get("hedges_won"),
                "hedges_rescued": router.get("hedges_rescued"),
                "reroutes": router.get("reroutes"),
                "stale_check": stale,
                "p99_pre_kill_s": ev.get("p99_pre_kill_s"),
                "p99_post_kill_s": p99_post,
                "max_p99_s": max_p99,
                "shm_client_pulls": ev.get("shm_client_pulls"),
                "min_fleet_requests": min_req,
            }

    # ------------------------------------------------ cell failover (r23)
    if expect.get("cell_failover"):
        ev: Dict[str, Any] = {}
        try:
            with open(os.path.join(workdir, "cell-evidence.json")) as f:
                ev = json.load(f)
        except (OSError, ValueError):
            pass
        if not ev:
            checks["cell_failover_survived"] = {
                "ok": False,
                "reason": "no cell-evidence.json in the workdir (drill "
                          "crashed before writing evidence)",
            }
        else:
            decision = ev.get("decision") or {}
            ship = ev.get("ship") or {}
            rpo = ev.get("rpo") or {}
            probes = ev.get("fence_probes") or []
            serve = ev.get("serve") or {}
            rollout = ev.get("rollout") or {}
            counters = ev.get("standby_counters") or {}
            refused = sum(1 for p in probes
                          if p.get("probe_rejected_stale_epoch"))
            min_refused = int(expect.get("min_fenced_refusals", 1))
            min_replayed = int(expect.get("min_replayed_subpushes", 1))
            min_segments = int(expect.get("min_shipped_segments", 1))
            max_rpo = expect.get("max_rpo_subpushes")
            lost = int(rpo.get("lost_total", -1))
            replayed = int(ev.get("replayed_beyond_snapshot", 0))
            budget = float(serve.get("rto_budget_s", 0.0) or 0.0)
            rto = float(serve.get("rto_s", -1.0))
            # Anti-vacuous, all the way down: the policy really ruled
            # promote on the shipped evidence; at least one COMPLETED
            # segment shipped and the standby really replayed shipped
            # sub-pushes past its snapshot (a run serving the snapshot
            # alone proves nothing about WAL shipping); the shipped tail
            # is an exact prefix of the acked ledger; the promoted tier
            # digest-matches the snapshot+tail reference over non-empty
            # digests; EVERY fenced probe was refused and at least
            # min_fenced_refusals fired; acked loss stays under the RPO
            # bound; the standby replica served a real score inside the
            # RTO budget; and the replicated rollout version loads
            # CRC-clean as the active version.
            ok = (bool(decision.get("promote"))
                  and int(ship.get("segments_completed", 0))
                  >= min_segments
                  and replayed >= min_replayed
                  and float(counters.get("wal_replayed_records", 0.0))
                  >= 1.0
                  and bool(ev.get("prefix_ok"))
                  and bool(ev.get("digests_match"))
                  and bool(ev.get("live_digests"))
                  and len(probes) >= 1
                  and refused == len(probes)
                  and refused >= min_refused
                  and lost >= 0
                  and (max_rpo is None or lost <= int(max_rpo))
                  and bool(serve.get("first_infer_ok"))
                  and 0.0 < rto <= budget
                  and bool(rollout.get("match"))
                  and bool(rollout.get("load_ok")))
            checks["cell_failover_survived"] = {
                "ok": ok,
                "decision": {k: decision.get(k)
                             for k in ("promote", "reason", "lag_bytes",
                                       "within_lag_slo",
                                       "snapshot_covered")},
                "shipped_segments": ship.get("segments_completed"),
                "min_shipped_segments": min_segments,
                "ship_gaps": ship.get("gaps"),
                "lag_bytes_at_kill": ev.get("lag_bytes_at_kill"),
                "rpo": rpo,
                "max_rpo_subpushes": max_rpo,
                "prefix_ok": ev.get("prefix_ok"),
                "prefix_mismatches": ev.get("prefix_mismatches"),
                "replayed_beyond_snapshot": replayed,
                "min_replayed_subpushes": min_replayed,
                "standby_counters": counters,
                "digests_match": ev.get("digests_match"),
                "live_digests": ev.get("live_digests", {}),
                "reference_digests": ev.get("reference_digests", {}),
                "fenced_refused": refused,
                "fenced_probes": len(probes),
                "min_fenced_refusals": min_refused,
                "probe_messages": [p.get("probe_message",
                                         p.get("probe_error", ""))
                                   for p in probes],
                "rto_s": rto,
                "rto_budget_s": budget,
                "promote_wall_s": (ev.get("promotion") or {}).get(
                    "promote_wall_s"),
                "rollout": rollout,
            }

    # ------------------------------------------------- production loop (r17)
    if expect.get("loop_exactly_once"):
        ev: Dict[str, Any] = {}
        try:
            with open(os.path.join(workdir, "loop-evidence.json")) as f:
                ev = json.load(f)
        except (OSError, ValueError):
            pass
        if not ev:
            checks["loop_exactly_once"] = {
                "ok": False,
                "reason": "no loop-evidence.json in the workdir (drill "
                          "crashed before writing evidence)",
            }
        else:
            emitted = int(ev.get("events_emitted", 0))
            min_events = int(expect.get("min_loop_events", 1))
            restored_events = int(ev.get("restored_cursor_events", -1))
            # Anti-vacuous, three ways: enough events flowed; the trainer
            # really died and resumed from a REAL joint checkpoint (not a
            # cold start); and the resume re-trained a non-empty window
            # (a kill that landed exactly on a checkpoint boundary would
            # prove nothing about the replay path).
            ok = (bool(ev.get("digests_match"))
                  and bool(ev.get("dense_match"))
                  and emitted >= min_events
                  and int(ev.get("final_cursor_events", -1)) == emitted
                  and int(ev.get("restarts", 0)) >= 1
                  and int(ev.get("restored_step", -1)) >= 1
                  and 1 <= restored_events < emitted
                  and int(ev.get("replayed_window", 0)) >= 1)
            checks["loop_exactly_once"] = {
                "ok": ok,
                "events_emitted": emitted,
                "min_loop_events": min_events,
                "final_cursor_events": ev.get("final_cursor_events"),
                "digests_match": ev.get("digests_match"),
                "dense_match": ev.get("dense_match"),
                "restarts": ev.get("restarts"),
                "restored_step": ev.get("restored_step"),
                "restored_cursor_events": restored_events,
                "replayed_window": ev.get("replayed_window"),
                "live_digests": ev.get("live_digests", {}),
                "reference_digests": ev.get("reference_digests", {}),
            }

    if expect.get("rollout_commit_gated"):
        ev = {}
        try:
            with open(os.path.join(workdir, "rollout-evidence.json")) as f:
                ev = json.load(f)
        except (OSError, ValueError):
            pass
        if not ev:
            checks["rollout_commit_gated"] = {
                "ok": False,
                "reason": "no rollout-evidence.json in the workdir "
                          "(drill crashed before writing evidence)",
            }
        else:
            swaps = ev.get("swaps", []) or []
            canary = ev.get("canary", {}) or {}
            rollback = ev.get("rollback", {}) or {}
            fb = ev.get("feedback", {}) or {}
            min_req = int(expect.get("min_rollout_requests", 1))
            min_swaps = int(expect.get("min_version_swaps", 2))
            ok = (not ev.get("errors")
                  and int(ev.get("requests", 0)) >= min_req
                  and int(ev.get("hard_failures", -1)) == 0
                  # Anti-vacuous: swaps really happened under load, AND
                  # a torn + a corrupt publication were really attempted
                  # — a run that never tore a publish proves nothing
                  # about the commit gate.
                  and len(swaps) >= min_swaps
                  and int(ev.get("torn_version", 0)) > 0
                  and not ev.get("torn_served", True)
                  and int(ev.get("corrupt_version", 0)) > 0
                  and not ev.get("corrupt_served", True)
                  and int(ev.get("corrupt_version", 0))
                  in (ev.get("quarantined") or [])
                  and bool(ev.get("promote_ok"))
                  and bool(rollback.get("ok"))
                  and int(canary.get("events", 0)) >= 1
                  and int(canary.get("misassigned_events", 1)) == 0
                  and 1 <= len(canary.get("sessions", []))
                  < int(canary.get("total_sessions", 0) or 1 << 30)
                  and int(fb.get("serve_events", 0)) >= 1)
            checks["rollout_commit_gated"] = {
                "ok": ok,
                "requests": ev.get("requests"),
                "hard_failures": ev.get("hard_failures"),
                "failure_samples": ev.get("failure_samples"),
                "version_swaps": len(swaps),
                "min_version_swaps": min_swaps,
                "torn_version": ev.get("torn_version"),
                "torn_served": ev.get("torn_served"),
                "corrupt_version": ev.get("corrupt_version"),
                "corrupt_served": ev.get("corrupt_served"),
                "quarantined": ev.get("quarantined"),
                "canary": canary,
                "promote_ok": ev.get("promote_ok"),
                "rollback": rollback,
                "feedback_serve_events": fb.get("serve_events"),
                "errors": ev.get("errors"),
                "min_rollout_requests": min_req,
            }

    # ---------------------------------------------------- retrieval (r17.4)
    if expect.get("retrieval_consistent"):
        ev = {}
        try:
            with open(os.path.join(workdir,
                                   "retrieval-evidence.json")) as f:
                ev = json.load(f)
        except (OSError, ValueError):
            pass
        if not ev:
            checks["retrieval_consistent"] = {
                "ok": False,
                "reason": "no retrieval-evidence.json in the workdir "
                          "(drill crashed before writing evidence)",
            }
        else:
            min_req = int(expect.get("min_retrieval_requests", 1))
            min_incr = int(expect.get("min_incremental_updates", 1))
            min_during = int(expect.get(
                "min_retrievals_during_update", 1))
            churn = ev.get("churn", {}) or {}
            flash = ev.get("flash", {}) or {}
            # The anchor: served candidates digest-match the brute-force
            # bypass witness; anti-vacuous: requests flowed, the index
            # really took incremental updates under live traffic, and no
            # request hard-failed across builder death / churn / flash.
            ok = (not ev.get("errors")
                  and bool(ev.get("digests_match"))
                  and int(ev.get("requests", 0)) >= min_req
                  and int(ev.get("hard_failures", -1)) == 0
                  and int(ev.get("incremental_updates", 0)) >= min_incr
                  and int(ev.get(
                      "retrievals_during_update", 0)) >= min_during)
            if expect.get("require_kill"):
                # The restore must be a real resume from a committed
                # (snapshot, cursor) pair — not a cold re-tail.
                ok = (ok and bool(ev.get("kill"))
                      and int(ev.get("restarts", 0)) >= 1
                      and int(ev.get("restored_version", 0)) >= 1
                      and int(ev.get("restored_cursor_records", 0)) >= 1)
            if expect.get("require_churn"):
                ok = (ok and len(churn.get("retired", [])) >= 1
                      and int(churn.get("retired_leaked", 1)) == 0)
            if expect.get("require_flash"):
                ok = (ok and bool(flash.get("within_slo"))
                      and float(flash.get("first_retrievable_s", 0)) > 0)
            checks["retrieval_consistent"] = {
                "ok": ok,
                "requests": ev.get("requests"),
                "hard_failures": ev.get("hard_failures"),
                "failure_samples": ev.get("failure_samples"),
                "digests_match": ev.get("digests_match"),
                "digest_served": ev.get("digest_served"),
                "digest_witness": ev.get("digest_witness"),
                "index_updates": ev.get("index_updates"),
                "incremental_updates": ev.get("incremental_updates"),
                "min_incremental_updates": min_incr,
                "retrievals_during_update":
                    ev.get("retrievals_during_update"),
                "min_retrievals_during_update": min_during,
                "restarts": ev.get("restarts"),
                "restored_version": ev.get("restored_version"),
                "restored_cursor_records":
                    ev.get("restored_cursor_records"),
                "churn": churn,
                "flash": flash,
                "errors": ev.get("errors"),
                "min_retrieval_requests": min_req,
            }

    # --------------------------------------------------- multi-tenant (r20)
    if expect.get("tenant_contention"):
        # Deferred import: chaos.invariants is imported BY sim.invariants
        # (the shared window/race cores) — a top-level import back into
        # the sim package would cycle through its __init__.
        from easydl_tpu.sim.multijob import check_tenants

        ev: Dict[str, Any] = {}
        try:
            with open(os.path.join(workdir, "tenant-evidence.json")) as f:
                ev = json.load(f)
        except (OSError, ValueError):
            pass
        if not ev:
            checks["tenant_contention"] = {
                "ok": False,
                "reason": "no tenant-evidence.json in the workdir (drill "
                          "crashed before writing evidence)",
            }
        else:
            # Policy checks over the RECORDED decisions/samples/moves —
            # the very checks the offline simulator's multi-job mode
            # runs, plus the byte-identity replay of the decision log
            # (tenant_replay_identical) through the pure arbiter.
            policy = check_tenants(ev, dict(expect),
                                   dict(ev.get("profile") or {}))
            checks.update(policy["checks"])
            # Per-job table isolation: every tenant's digests (full row
            # width — optimizer state included) match its own fault-free
            # reference, with anti-vacuous floors: >= 2 jobs, every job
            # actually pushed, zero hard storm failures.
            jobs = dict(ev.get("jobs") or {})
            if expect.get("tenant_isolated"):
                per_job = {
                    name: {
                        "digests_match": bool(j.get("digests_match")),
                        "pushes": int((j.get("storm") or {})
                                      .get("pushes", 0)),
                        "hard_failures": int((j.get("storm") or {})
                                             .get("hard_failures", -1)),
                        "errors": (j.get("storm") or {}).get("errors"),
                    }
                    for name, j in sorted(jobs.items())
                }
                ok = (len(per_job) >= 2
                      and all(v["digests_match"] for v in per_job.values())
                      and all(v["pushes"] >= 1 for v in per_job.values())
                      and all(v["hard_failures"] == 0
                              for v in per_job.values()))
                checks["tenant_isolated"] = {"ok": ok, "jobs": per_job}
            # Drain-before-kill on every actuated preemption: the
            # victim's own quiesce_exit timeline record precedes the
            # fleet's stop mark, the worker was provably dead at the
            # stop, and no drain escalated. Vacuous-pass refused.
            if expect.get("drain_before_kill"):
                drains = list(ev.get("preempt_drains") or [])
                if not drains:
                    checks["tenant_drain_before_kill"] = {
                        "ok": False,
                        "reason": "no preemption was actuated — the "
                                  "drain path was never exercised "
                                  "(vacuous)",
                    }
                else:
                    races = []
                    for d in drains:
                        # Timeline records are wall-clock; the fleet's
                        # marks are drill-relative — the drain is judged
                        # on its OWN evidence pair: a quiesce_exit
                        # recorded at all, worker dead at the stop, and
                        # no escalation.
                        races.append({
                            "job": d.get("job"), "agent": d.get("agent"),
                            "quiesce_exits": d.get("quiesce_exits"),
                            "worker_alive_at_stop":
                                bool(d.get("worker_alive_at_stop")),
                            "escalated": bool(d.get("escalated")),
                            "won": (bool(d.get("quiesce_exits"))
                                    and not d.get("worker_alive_at_stop")
                                    and not d.get("escalated")),
                        })
                    checks["tenant_drain_before_kill"] = {
                        "ok": all(r["won"] for r in races),
                        "races": races,
                    }

    # ------------------------------------------------- detection (alerting)
    # The drill's alerting witness (harness AlertRecorder) leaves
    # alert-evidence.json; ``detect`` requires the named SLO alert to fire
    # within the TTD budget AND clear after recovery AND the recorded
    # decision log to re-derive byte-identically; ``detect_none`` is the
    # anti-vacuous negative control — a fault-free run must page ZERO.
    detect = expect.get("detect")
    if detect is not None:
        checks["detected_and_cleared"] = _check_detected(
            dict(detect), _read_alert_evidence(workdir), kills=kills)
    if expect.get("detect_none"):
        checks["no_false_pages"] = _check_no_false_pages(
            _read_alert_evidence(workdir))

    # ----------------------------------------------------- faults cross-check
    min_faults = expect.get("min_faults")
    if min_faults is not None:
        total = float(sum((fault_counts or {}).values()))
        checks["faults_observed"] = {
            "ok": total >= float(min_faults),
            "observed": total, "min_faults": float(min_faults),
            "by_kind": dict(fault_counts or {}),
        }

    return {
        "passed": all(c["ok"] for c in checks.values()),
        "checks": checks,
    }


def _read_alert_evidence(workdir: str) -> Optional[Dict[str, Any]]:
    try:
        with open(os.path.join(workdir, "alert-evidence.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _fault_time(evidence: Mapping[str, Any],
                kills: Optional[List[Mapping[str, Any]]]) -> Optional[float]:
    """Wall-clock moment the drill's first fault landed: the earliest
    harness kill mark, else the armed plan's first event (t0 + start_s),
    else the drill start — TTD is measured from here."""
    ctx = dict(evidence.get("fault_context") or {})
    candidates: List[float] = []
    for mark in (list(ctx.get("kill_marks") or [])
                 + list(ctx.get("fault_marks") or [])
                 + list(kills or [])):
        t = mark.get("t")
        if t is not None:
            candidates.append(float(t))
    plan = dict(ctx.get("plan") or {})
    t0 = plan.get("t0")
    if t0 is not None:
        starts = [float(e.get("start_s", 0.0))
                  for e in plan.get("events") or []]
        if starts:
            candidates.append(float(t0) + min(starts))
    if candidates:
        return min(candidates)
    start = ctx.get("t0")
    return float(start) if start is not None else None


def _check_detected(detect: Dict[str, Any],
                    evidence: Optional[Mapping[str, Any]],
                    kills: Optional[List[Mapping[str, Any]]] = None
                    ) -> Dict[str, Any]:
    """detected_and_cleared: the expected alert fired within the TTD
    budget, cleared after recovery, and the alert-decision replay is
    byte-identical and non-empty. A drill that ran without its witness
    is a FAILURE, not a skip — detection claims must never pass
    vacuously."""
    from easydl_tpu.utils.env import knob_float

    alert = str(detect.get("alert", ""))
    out: Dict[str, Any] = {"ok": False, "alert": alert}
    if not evidence:
        out["reason"] = ("no alert-evidence.json — the drill ran without "
                         "its alerting witness (vacuous)")
        return out
    budget = float(detect.get("ttd_budget_s",
                              knob_float("EASYDL_ALERT_TTD_BUDGET_S")))
    rounds = int(evidence.get("rounds", 0))
    fault_t = _fault_time(evidence, kills)
    # TTD anchors on the first firing transition AT/AFTER the fault (1s
    # clock-rounding slack): drill setup is legitimate churn — a job
    # placing its workers reshapes, and that setup-phase firing must not
    # be mistaken for (or poison) detection of the fault injected later.
    fired_t = None
    for tr in evidence.get("transitions") or []:
        if (str(tr.get("slo")) == alert and tr.get("to") == "firing"
                and (fault_t is None
                     or float(tr.get("t", 0.0)) >= float(fault_t) - 1.0)):
            fired_t = float(tr["t"])
            break
    replay = dict(evidence.get("replay") or {})
    ttd = (round(float(fired_t) - float(fault_t), 3)
           if fired_t is not None and fault_t is not None else None)
    # "cleared" = a clear transition AFTER the first fire. Judged from
    # the timeline, not the final state: drill teardown SIGKILLs its own
    # subprocess fleet, and the recorder's last ticks legitimately see
    # that carnage re-fire scrape alerts — the detection claim is about
    # the drill's recovery, which happened earlier.
    cleared = False
    if fired_t is not None:
        for tr in evidence.get("transitions") or []:
            if (str(tr.get("slo")) == alert and tr.get("to") == "clear"
                    and float(tr.get("t", 0.0)) >= float(fired_t)):
                cleared = True
                break
    out.update({
        "rounds": rounds,
        "fired": fired_t is not None,
        "fault_t": fault_t,
        "fired_t": fired_t,
        "ttd_s": ttd,
        "ttd_budget_s": budget,
        "cleared": cleared,
        "replay_decisions": int(replay.get("decisions", 0)),
        "replay_identical": bool(replay.get("identical")),
    })
    # small negative slack: clock rounding between the kill mark and the
    # recorder tick; an alert firing well BEFORE its fault is a policy
    # bug, not a detection
    out["ok"] = bool(
        rounds > 0
        and ttd is not None
        and -1.0 <= ttd <= budget
        and out["cleared"]
        and out["replay_identical"]
        and out["replay_decisions"] > 0
    )
    return out


def _check_no_false_pages(evidence: Optional[Mapping[str, Any]]
                          ) -> Dict[str, Any]:
    """The negative control: a fault-free run must fire ZERO
    page-severity alerts (tickets are allowed — planned churn is
    ticket-worthy, never page-worthy), with the witness provably
    running and its decision log replaying byte-identically."""
    out: Dict[str, Any] = {"ok": False}
    if not evidence:
        out["reason"] = ("no alert-evidence.json — the negative control "
                         "ran without its alerting witness (vacuous)")
        return out
    rounds = int(evidence.get("rounds", 0))
    replay = dict(evidence.get("replay") or {})
    out.update({
        "rounds": rounds,
        "pages_fired": list(evidence.get("pages_fired") or []),
        "replay_decisions": int(replay.get("decisions", 0)),
        "replay_identical": bool(replay.get("identical")),
    })
    out["ok"] = bool(
        rounds > 0
        and not out["pages_fired"]
        and out["replay_identical"]
        and out["replay_decisions"] > 0
    )
    return out


def _straggler_onset(workdir: str, agent: str) -> Optional[float]:
    """Wall-clock start of the armed straggler window targeting ``agent``
    (t0 + start_s from the harness' chaos-plan.json)."""
    try:
        with open(os.path.join(workdir, "chaos-plan.json")) as f:
            plan = json.load(f)
    except (OSError, ValueError):
        return None
    t0 = plan.get("t0")
    if t0 is None:
        return None
    starts = [
        float(t0) + float(e.get("start_s", 0.0))
        for e in plan.get("events", [])
        if e.get("kind") == "straggler"
        and str(e.get("target", {}).get("agent", "")) == agent
    ]
    return min(starts) if starts else None

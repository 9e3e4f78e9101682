"""Measure cell-failover RPO/RTO and merge a ``cell_failover`` section
into RECOVERY.json.

ROADMAP item 5's north-star numbers are the two a disaster-recovery story
is judged by:

- **RPO** (recovery point): how much ACKED work the standby may lose when
  the primary cell vanishes without warning. Measured, not estimated —
  the drill freezes the WAL shipper un-drained at the kill (a real cell
  loss takes the source disk with it), decodes the standby's shipped WAL
  tail, and counts the acked sub-pushes that never arrived, having first
  proven the shipped tail an exact prefix of the acked ledger and the
  promoted tier bit-identical to snapshot + tail.
- **RTO** (recovery time): cell-dark → a standby serving replica
  answering real scores through the router, decomposed into the
  promotion half (fence + rescue-boot + publish above the epoch floors)
  and the serve half.

The numbers come from the same ``cell_failover`` chaos scenario that
gates CI (scenarios/cell_failover.yaml) — this script just runs it and
reduces the evidence, so the benchmark can never drift from the drill.

Usage: python scripts/bench_failover.py [--out RECOVERY.json] [--seed N]
Must run where jax can use a CPU platform; spawns its own subprocess with
the forced-CPU env (like chaos_run.py) if the current backend is not.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from easydl_tpu.utils.env import rerun_on_cpu_mesh  # noqa: E402


def _section(verdict: dict) -> dict:
    ev = verdict.get("cell") or {}
    ship = ev.get("ship") or {}
    rpo = ev.get("rpo") or {}
    serve = ev.get("serve") or {}
    promo = ev.get("promotion") or {}
    decision = ev.get("decision") or {}
    acked = int(rpo.get("acked_total", 0))
    lost = int(rpo.get("lost_total", 0))
    return {
        "scenario": "cell_failover (SIGKILL every primary-cell process "
                    "mid-push-storm; fenced promotion of the shipped "
                    "standby)",
        "passed": bool(verdict.get("passed")),
        "ps_shards": len((rpo.get("per_shard") or {})) or None,
        "rpo": {
            "acked_subpushes_in_window": acked,
            "applied_on_standby": int(rpo.get("applied_total", 0)),
            "lost_subpushes": lost,
            "lost_fraction": round(lost / acked, 4) if acked else None,
            "replication_lag_bytes_at_kill": ev.get("lag_bytes_at_kill"),
            "ship_interval_s": ev.get("ship_interval_s"),
            "prefix_exact": bool(ev.get("prefix_ok")),
            "digests_bit_identical": bool(ev.get("digests_match")),
        },
        "rto": {
            "promote_to_first_served_score_s": serve.get("rto_s"),
            "rto_budget_s": serve.get("rto_budget_s"),
            "promotion_s": promo.get("promote_wall_s"),
            "first_infer_ok": bool(serve.get("first_infer_ok")),
        },
        "fencing": {
            "probes": len(ev.get("fence_probes") or []),
            "refused": sum(
                1 for p in (ev.get("fence_probes") or [])
                if p.get("probe_rejected_stale_epoch")),
        },
        "promotion_decision": {k: decision.get(k) for k in
                               ("promote", "reason", "within_lag_slo",
                                "snapshot_covered")},
        "ship_totals": ship,
        "wall_s": verdict.get("wall_s"),
    }


def main() -> None:
    ap = argparse.ArgumentParser(
        description="measure cell-failover RPO/RTO into RECOVERY.json")
    ap.add_argument("--out", default=os.path.join(REPO, "RECOVERY.json"))
    ap.add_argument("--seed", type=int, default=None)
    args = ap.parse_args()

    # The drill's PS pods need a CPU platform.
    rerun_on_cpu_mesh(__file__, "EASYDL_CHAOS_CHILD")

    from easydl_tpu.chaos.harness import run_scenario

    verdict = run_scenario("cell_failover", seed=args.seed)
    section = _section(verdict)
    result = {"measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
              "cell_failover": section}
    # Merge, don't clobber: measure_recovery/measure_longwindow own their
    # own top-level sections of the same file.
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prior = json.load(f)
            for key, val in prior.items():
                result.setdefault(key, val)
        except (OSError, ValueError):
            pass
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps({"cell_failover": section}, indent=2))
    if not section["passed"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

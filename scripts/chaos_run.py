"""Run chaos drills against the simulated-distributed runtime and write one
``CHAOS_r*_<scenario>.json`` verdict per scenario.

The executable half of the chaos subsystem (docs/design/chaos.md): each
scenario launches a real job (gRPC master + agents + jax.distributed worker
subprocesses on the forced CPU mesh, PS pods where the scenario needs them),
injects its seed-deterministic fault schedule, and asserts the recovery
invariants. Exit code is non-zero when any scenario's invariants fail — this
is a gate, not a report.

Usage::

    python scripts/chaos_run.py                       # every scenario
    python scripts/chaos_run.py --scenario worker_kill
    python scripts/chaos_run.py --scenario master_crash   # failover drill
    python scripts/chaos_run.py --scenario rpc_burst --seed 99
    python scripts/chaos_run.py --list

Must run where jax can use a CPU platform; spawns its own subprocess with
the forced-CPU env (like measure_recovery.py) if the current backend is not.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from easydl_tpu.utils.env import rerun_on_cpu_mesh  # noqa: E402


def next_round(out_dir: str) -> int:
    rounds = [0]
    for path in glob.glob(os.path.join(out_dir, "CHAOS_r*.json")):
        m = re.match(r"CHAOS_r(\d+)", os.path.basename(path))
        if m:
            rounds.append(int(m.group(1)))
    return max(rounds) + 1


def main() -> None:
    ap = argparse.ArgumentParser(description="easydl_tpu chaos drills")
    ap.add_argument("--scenario", action="append", default=None,
                    help="scenario name (repeatable; default: all)")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the scenario's default seed")
    ap.add_argument("--out-dir", default=REPO,
                    help="where CHAOS_r*.json verdicts land")
    ap.add_argument("--round", type=int, default=None,
                    help="verdict round number (default: auto-increment)")
    ap.add_argument("--keep-workdir", action="store_true",
                    help="keep each scenario's job workdir for autopsy")
    ap.add_argument("--list", action="store_true",
                    help="list scenarios and exit")
    args = ap.parse_args()

    if not args.list:
        # The drills need a multi-device CPU platform.
        rerun_on_cpu_mesh(__file__, "EASYDL_CHAOS_CHILD")

    from easydl_tpu.chaos.harness import SCENARIOS, run_scenario

    if args.list:
        for name, builder in SCENARIOS.items():
            sc = builder()
            print(f"{name:24s} seed={sc.chaos.seed:<4d} "
                  f"tier={sc.tier:7s} {sc.chaos.notes}")
        return

    names = args.scenario or list(SCENARIOS)
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        raise SystemExit(f"unknown scenario(s) {unknown}; "
                         f"known: {sorted(SCENARIOS)}")
    os.makedirs(args.out_dir, exist_ok=True)
    rnd = args.round if args.round is not None else next_round(args.out_dir)
    failed = []
    for name in names:
        t0 = time.monotonic()
        print(f"=== chaos scenario {name} (round {rnd}) ===", flush=True)
        verdict = run_scenario(name, seed=args.seed,
                               keep_workdir=args.keep_workdir)
        out = os.path.join(args.out_dir, f"CHAOS_r{rnd:02d}_{name}.json")
        with open(out, "w") as f:
            json.dump(verdict, f, indent=2)
            f.write("\n")
        status = "PASS" if verdict["passed"] else "FAIL"
        print(f"{status} {name} in {time.monotonic() - t0:.1f}s -> {out}",
              flush=True)
        for check, doc in verdict["invariants"]["checks"].items():
            print(f"  [{'ok' if doc['ok'] else 'VIOLATED'}] {check}")
        if not verdict["passed"]:
            failed.append(name)
    if failed:
        raise SystemExit(f"chaos scenarios FAILED: {failed}")


if __name__ == "__main__":
    main()

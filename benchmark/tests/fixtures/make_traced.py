#!/usr/bin/env python3
"""One traced chip run of a cell, cut down to what the per-layer readers
read, with every reader's value beside it — ``tests/test_traced_fixtures.py``
holds the readers to these on the CPU.

    python3 benchmark/run.py --workload <cell> --seed <n> --trace 1
    python3 benchmark/tests/fixtures/make_traced.py <cell> <out directory> \\
        [--parent <a checkout of an older commit>]
    python3 benchmark/tests/fixtures/make_traced.py <cell> <directory> \
        --parent <checkout> --again     # parent_values of a fixture that is there

Reads ``benchmark/.work/<cell>/artifacts.json`` and the run's trace (so it
runs on the machine the run was made on, right behind it). Writes
``traced_<cell>.json``: the artifacts without what no reader of a steady
cell looks at, the trace as ``lib/scope_reduce.of_run`` reduces it — paths
(through a table: thousands of operations share a few hundred) and self
seconds of the operations that ran, nothing else — ``values``: EVERY reader
file's value on it, listed for the cell or not (None where it finds nothing
to read), and with ``--parent`` ``parent_values``: the SAVED run — the fixture
itself, stood in for the trace as the test stands it in — read by that
checkout's readers, under its names, and ``parent_listed``: the names its
``BENCHMARK.json`` lists for the cell (PR 50: the commit before the merges).
Both sides read the fixture because two loads of one trace file give an
operation's events in another order, and a sum of them its last digits.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
KEPT = ("config", "traffic", "device", "chips", "memory_peak_bytes",
        "allocator_peak_bytes", "setup_s", "window_s", "steps",
        "tokens_per_step", "step_s", "attempted", "failed", "correct",
        "compile", "step_memory", "n_params", "flash_calls", "busy")


def stand_in(bench_dir: str, path: str) -> dict:
    """The fixture at ``path``, its reduction stood in for the trace's in
    ``bench_dir``'s ``lib`` (whose readers then read it without a trace)."""
    sys.path.insert(0, bench_dir)
    from lib import scope_names, scope_reduce

    with open(path) as f:
        fixture = json.load(f)
    reduced = fixture["reduced"]
    table = reduced["path_table"]
    found = dict(reduced, paths={op: table[i]
                                 for op, i in reduced["paths"].items()})
    scope_reduce.of_run = lambda artifacts: (
        found if artifacts.get("trace_summary") else None)
    scope_reduce.trace_file = lambda: path
    scope_names._self_seconds = lambda path, mtime: fixture["self_seconds"]
    return fixture


def read_all(bench_dir: str, artifacts: dict) -> dict:
    """``{reader: value}`` of every file under ``layer_metrics``."""
    out = {}
    directory = os.path.join(bench_dir, "layer_metrics")
    for name in sorted(f[:-3] for f in os.listdir(directory)
                       if f.endswith(".py")):
        spec = importlib.util.spec_from_file_location(
            f"reader_{name}", os.path.join(directory, f"{name}.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        try:
            value = module.read(artifacts)
        except Exception as e:  # a reader of another kind of cell
            value = f"raised {type(e).__name__}: {e}"
        out[name] = value
    return out


def build(cell: str, artifacts: dict, found: dict, seconds: dict,
          values: dict) -> dict:
    """The fixture of one run: ``found`` is ``scope_reduce.of_run``'s,
    ``seconds`` the self seconds by operation."""
    called = {c["name"] for c in artifacts.get("flash_calls", ())}
    ran = [op for op in found["paths"]
           if seconds.get(op, 0.0) > 0.0 or op in called]
    table = sorted({found["paths"][op] for op in ran})
    index = {path: i for i, path in enumerate(table)}
    summary = artifacts["trace_summary"]
    return {
        "about": f"{cell} on {artifacts['device']['count']} x "
                 f"{artifacts['device']['kind']}, one --trace 1 run; made by "
                 "benchmark/tests/fixtures/make_traced.py (its docstring says "
                 "what is kept)",
        "artifacts": dict(
            {k: artifacts[k] for k in KEPT if k in artifacts},
            trace_summary=dict(
                {k: v for k, v in summary.items() if k != "ops"},
                ops={op: row for op, row in summary["ops"].items()
                     if op in called})),
        "reduced": dict(
            {k: v for k, v in found.items() if k != "paths"},
            path_table=table,
            paths={op: index[found["paths"][op]] for op in ran}),
        "self_seconds": {op: seconds[op] for op in ran if op in seconds},
        "values": values,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("out")
    ap.add_argument("--parent")
    ap.add_argument("--again", action="store_true",
                    help="the fixture is there: only its parent's values")
    ap.add_argument("--readers-of", help=argparse.SUPPRESS)
    args = ap.parse_args()
    path = os.path.join(args.out, f"traced_{args.cell}.json")
    if args.readers_of:  # the child: another checkout's readers
        fixture = stand_in(args.readers_of, path)
        print(json.dumps(read_all(args.readers_of, fixture["artifacts"])))
        return 0

    if args.again:
        with open(path) as f:
            fixture = json.load(f)
    else:
        with open(os.path.join(BENCH, ".work", args.cell,
                               "artifacts.json")) as f:
            artifacts = json.load(f)
        sys.path.insert(0, BENCH)
        from lib import scope_names, scope_reduce
        trace_path = scope_reduce.trace_file()
        assert trace_path and args.cell in trace_path, trace_path
        found = scope_reduce.of_run(artifacts)
        seconds = scope_names._self_seconds(trace_path,
                                            os.path.getmtime(trace_path))
        fixture = build(args.cell, artifacts, found, seconds,
                        read_all(BENCH, artifacts))
        os.makedirs(args.out, exist_ok=True)
    if args.parent:
        with open(path, "w") as f:  # the child reads it
            json.dump(fixture, f, separators=(",", ":"))
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), args.cell, args.out,
             "--readers-of", os.path.join(os.path.abspath(args.parent),
                                          "benchmark")],
            capture_output=True, text=True, check=True)
        fixture["parent_values"] = json.loads(
            child.stdout.strip().splitlines()[-1])
        with open(os.path.join(args.parent, "BENCHMARK.json")) as f:
            fixture["parent_listed"] = sorted(
                m["name"] for m in json.load(f)["per_layer"]
                if args.cell in m.get("workloads", [args.cell]))
    with open(path, "w") as f:
        json.dump(fixture, f, separators=(",", ":"))
    print(f"{path}: {os.path.getsize(path)} bytes, "
          f"{len(fixture['reduced']['paths'])} operations, "
          f"{len(fixture['reduced']['path_table'])} paths")
    for name, value in fixture["values"].items():
        if value is not None:
            print(f"  {name} = {value}")
    for name, value in sorted(fixture.get("parent_values", {}).items()):
        if value is not None:
            print(f"  parent {name} = {value}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name, from data:

- the cell (``workloads`` of ``BENCHMARK.json``) names a configuration and a
  traffic mix;
- ``configs[].file`` is the configuration as it is run;
- ``benchmark/traffic/<traffic>.json`` is the mix, and names its ``driver``;
- ``benchmark/drivers/<driver>.py`` runs a kind of traffic: ``run(run)``
  returns the run's artifacts, a plain dict;
- ``benchmark/end_to_end/<metric>.py`` and ``benchmark/layer_metrics/
  <metric>.py`` each hold ``read(artifacts)``, which returns the metric's
  value, or None where the artifacts have nothing for it (the metric is
  then left out of the line).

So a later PR adds a configuration, a mix, a driver or a metric by adding
files and entries; this file has no branch on any of their names. With
``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy time (``busy`` among
the artifacts) and the ``breakdown``; where a driver keeps the numbers that
``correct`` compared (``compared``: name, number, limit) they are the line's
last key and the last lines of standard error. The last line
of standard output is the result; a run that cannot measure (no accelerator,
too few chips, no program beside the benchmark) exits non-zero without one.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass(frozen=True)
class Run:
    """What a driver is given."""

    root: str            # the checkout
    workdir: str         # this run's own directory, emptied before the run
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float
    #: keep a description of the raw trace among the artifacts (for looking
    #: at a trace by hand; costs time, off in a measured run)
    keep_raw: bool = False


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    """``<benchmark>/<directory>/<name>.py`` as a module, by file path."""
    path = os.path.join(HERE, directory, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{name}".replace("-", "_").replace(".", "_"),
        path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"benchmark: no {directory}/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def by_name(entries, name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: BENCHMARK.json has no {what} {name!r}")


def read_metrics(bench: Dict[str, Any], section: str, directory: str,
                 cell: str, artifacts: Dict[str, Any]) -> Dict[str, Any]:
    """The cell's metrics of one section, each from its own reader."""
    out = {}
    for metric in bench[section]:
        if cell not in metric.get("workloads", [cell]):
            continue
        value = load_module(directory, metric["name"]).read(artifacts)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-json",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--keep-raw", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "easydl_tpu")):
        print("benchmark: no easydl_tpu/ beside benchmark/ — this measures "
              "the repository and is nothing without it", file=sys.stderr)
        return 2
    for path in (HERE, ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    bench = load_json(args.benchmark_json)
    cell = by_name(bench["workloads"], args.workload, "workload")
    config_entry = by_name(bench["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
    driver = load_module("drivers", traffic["driver"])
    workdir = os.path.join(HERE, ".work", cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)

    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    artifacts = driver.run(Run(
        root=ROOT, workdir=workdir, cell=cell, config=config,
        traffic=traffic, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), t_start=T_START, keep_raw=args.keep_raw))
    artifacts.setdefault("config", config)
    artifacts.setdefault("traffic", traffic)

    section, directory = (("per_layer", "layer_metrics") if args.trace
                          else ("end_to_end", "end_to_end"))
    line = {
        "correct": bool(artifacts["correct"]),
        "attempted": int(artifacts["attempted"]),
        "failed": int(artifacts["failed"]),
        "metrics": read_metrics(bench, section, directory, cell["name"],
                                artifacts),
        "device": dict(artifacts["device"], memory_peak_bytes=int(
            artifacts["memory_peak_bytes"])),
    }
    if args.trace:
        # how long the device was busy in the traced window, and where the
        # time went: from the trace, or as the driver could tell
        line["device"].update(artifacts.get("busy", {}))
        if artifacts.get("breakdown"):
            line["breakdown"] = artifacts["breakdown"]
    # each number `correct` compared, beside its limit: the line's last key
    # and the last lines of standard error (a driver that keeps none: none)
    if artifacts.get("compared"):
        line["compared"] = artifacts["compared"]
        for name, (number, limit) in line["compared"].items():
            print(f"benchmark: compared {name} {number!r} limit {limit!r}",
                  file=sys.stderr)
    with open(os.path.join(workdir, "artifacts.json"), "w") as f:
        json.dump(artifacts, f, default=str)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

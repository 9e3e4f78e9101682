"""virtual-clock-purity: replayed policy code never touches the real world.

The discipline (PR 8): the offline simulator replays the REAL policy
objects — Rendezvous, Autoscaler, StragglerDetector — on a virtual clock,
and its guarantee is byte-identical verdicts across runs. That guarantee
dies the moment any module the simulator replays reads wall-clock time or
a process-global RNG: the replay becomes timing-dependent, the negative
controls go flaky, and ``chaos_smoke.sh``'s byte-compare gate starts
failing on innocent changes. This rule pins the purity statically for
``easydl_tpu/sim/`` and the policy modules the simulator imports
(``brain/policy.py``, ``brain/straggler.py``, ``elastic/membership.py``):

* no CALLS to ``time.time``/``time.monotonic``/``time.perf_counter``/
  ``time.sleep``, ``datetime.now``-family, or module-global ``random.*``
  / ``numpy.random.*`` functions;
* no REFERENCES to those symbols either (``field(default_factory=
  time.monotonic)`` reads the real clock at dataclass construction) —
  EXCEPT in a function signature's default-value position, which is the
  sanctioned injection seam (``clock: Callable = time.monotonic``).

``random.Random(seed)`` stays legal: a seeded instance is deterministic
state the caller owns, exactly what the simulator injects.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from easydl_tpu.analysis.core import (
    Finding,
    Rule,
    ScopedVisitor,
    dotted_name,
)

#: Modules the PR-8 simulator replays — the byte-identical set.
PURE_PREFIXES = ("easydl_tpu/sim/",)
PURE_PATHS = (
    "easydl_tpu/brain/alert_policy.py",
    "easydl_tpu/brain/arbiter.py",
    "easydl_tpu/brain/mesh_policy.py",
    "easydl_tpu/brain/policy.py",
    "easydl_tpu/brain/straggler.py",
    "easydl_tpu/brain/tier_policy.py",
    "easydl_tpu/cell/policy.py",
    "easydl_tpu/core/mesh_shapes.py",
    "easydl_tpu/elastic/goodput.py",
    "easydl_tpu/elastic/membership.py",
    "easydl_tpu/loop/rollout.py",
    "easydl_tpu/retrieval/policy.py",
    "easydl_tpu/serve/routing.py",
)

_CLOCK_NAMES = frozenset((
    "time.time", "time.monotonic", "time.perf_counter", "time.sleep",
    "time.time_ns", "time.monotonic_ns", "time.perf_counter_ns",
    "datetime.now", "datetime.utcnow", "datetime.today",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "date.today", "datetime.date.today",
))


def _impurity(name: Optional[str]) -> Optional[str]:
    if not name:
        return None
    if name in _CLOCK_NAMES:
        return name
    parts = name.split(".")
    # module-global RNG: random.random / random.shuffle / np.random.rand …
    # but random.Random is a seeded, injectable instance — allowed.
    if parts[0] == "random" and len(parts) > 1 and parts[1] != "Random":
        return name
    if "random" in parts[1:-1] or (len(parts) > 2 and parts[-2] == "random"):
        return name
    return None


def _default_expr_ids(fn) -> Set[int]:
    """ids of every node inside a signature's default values — the
    injection seam where `clock=time.monotonic` is the point."""
    out: Set[int] = set()
    args = fn.args
    for d in list(args.defaults) + [d for d in args.kw_defaults if d]:
        for sub in ast.walk(d):
            out.add(id(sub))
    return out


class _Visitor(ScopedVisitor):
    def __init__(self, rule: str, path: str):
        super().__init__(rule, path)
        self._allowed: Set[int] = set()
        self._flagged: Set[int] = set()

    def _scoped_fn(self, node) -> None:
        self._allowed |= _default_expr_ids(node)
        ScopedVisitor.visit_FunctionDef(self, node)

    visit_FunctionDef = _scoped_fn
    visit_AsyncFunctionDef = _scoped_fn

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._allowed |= _default_expr_ids(node)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        bad = _impurity(dotted_name(node))
        if (bad and id(node) not in self._allowed
                and id(node) not in self._flagged):
            # mark sub-attributes so datetime.datetime.now emits once
            for sub in ast.walk(node):
                self._flagged.add(id(sub))
            self.emit(node, bad,
                      f"reference to {bad} in a simulator-replayed module "
                      "— use the injected clock/rng (byte-identical replay,"
                      " PR 8) or take it as a default-arg injection seam")
        self.generic_visit(node)


class VirtualClockPurity(Rule):
    name = "virtual-clock-purity"
    invariant = ("Modules the offline simulator replays use only the "
                 "injected clock/rng — never wall clock, datetime.now, or "
                 "process-global random — so replay verdicts stay "
                 "byte-identical.")

    def check(self, path: str, tree: ast.Module,
              source: str) -> List[Finding]:
        if not (path.startswith(PURE_PREFIXES) or path in PURE_PATHS):
            return []
        v = _Visitor(self.name, path)
        v.visit(tree)
        return v.findings

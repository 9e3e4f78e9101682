"""Mesh-shape policy: which (data x model [x pipeline]) factorization a
generation should run — decided from observed per-shape throughput/MFU.

ROADMAP item 1's control half (PR 12). Elastic generation switches used
to take the mesh shape verbatim from static job config; now membership
enumerates the valid factorizations of the surviving world size
(:func:`easydl_tpu.core.mesh_shapes.enumerate_shapes`) and THIS policy
picks among them:

- **cold start**: the first candidate in enumeration order — the widest
  data axis that satisfies the model's divisibility + memory constraints
  (pure DP when the model fits one chip; the narrowest model sharding
  that fits otherwise);
- **refine from measurements**: once the running shape has
  ``min_samples`` observed throughput samples, unmeasured candidates are
  PROBED (one planned reshape each, budgeted by ``max_probes_per_world``
  and paced by ``probe_cooldown_s``), then the measured-best shape is
  adopted — with a ``improvement_floor`` hysteresis so near-ties never
  flap the mesh;
- **pinned override**: an operator pin (job config / EASYDL_MESH_PIN)
  short-circuits everything — the runbook's escape hatch. A pin that is
  not a valid shape for the current world falls back to the policy with
  a warning rather than wedging the job.

Pure by design, same contract as ``brain/policy.py`` /
``brain/straggler.py`` (easylint rule 5): no IO, no clock of its own —
every query carries an explicit ``now`` — so the exact same object runs
inside the live master's tick loop AND inside the offline control-plane
simulator, and replay verdicts stay byte-identical. The throughput
signal it consumes is the one the ``easydl_worker_mfu`` gauge reports:
the program's one MFU definition (:mod:`easydl_tpu.core.mfu`).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

from easydl_tpu.core.mesh_shapes import (
    MeshConstraints, MeshSpec, enumerate_shapes, validate_shape,
)
from easydl_tpu.utils.logging import get_logger

log = get_logger("brain", "mesh_policy")


@dataclass(frozen=True)
class MeshPolicyConfig:
    """Damping/budget knobs for the shape decision."""

    #: throughput samples at a shape before its estimate is trusted
    min_samples: int = 3
    #: sliding window per (world, shape)
    window: int = 16
    #: a measured challenger must beat the current shape's mean by this
    #: factor to be adopted (anti-flap hysteresis for near-ties)
    improvement_floor: float = 1.02
    #: unmeasured-candidate probes per world size (each costs a reshape)
    max_probes_per_world: int = 4
    #: seconds between policy-initiated mesh reshapes
    probe_cooldown_s: float = 10.0
    #: consecutive formations allowed to HOLD an under-measured current
    #: shape before abandoning it for the measured best — the escape from
    #: a probed shape whose workers crash before producing a sample
    #: (each hold is one re-formation, i.e. one crash-loop turn)
    max_unmeasured_holds: int = 3

    @classmethod
    def from_dict(cls, doc) -> "MeshPolicyConfig":
        fields = {f for f in cls.__dataclass_fields__}  # type: ignore[attr-defined]
        return cls(**{k: v for k, v in dict(doc).items() if k in fields})


def mesh_shape_decision(
    candidates: Tuple[MeshSpec, ...],
    history: Dict[str, Tuple[int, float]],
    current: Optional[str],
    probes_used: int,
    config: MeshPolicyConfig,
    pinned: str = "",
    world: int = 0,
    holds: int = 0,
    bad: frozenset = frozenset(),
) -> Tuple[str, Dict[str, object]]:
    """The pure decision core: ``(chosen_key, decision_inputs)``.

    ``history`` maps shape key -> (sample count, mean samples/sec) for
    this world size; ``current`` is the shape the running generation uses
    (None before any formation); ``probes_used`` is how many probe
    reshapes this world has already spent. The returned inputs dict is
    what the master stamps into its WAL — drill forensics can reconstruct
    exactly why a shape was picked.

    ``bad`` shapes (abandoned after crash-looping unmeasured — the
    Autoscaler's bad-size memory, applied to factorizations) are dropped
    from the candidate list outright: never re-probed, never re-adopted.
    """
    if bad:
        candidates = tuple(c for c in candidates if c.key() not in bad)
    inputs: Dict[str, object] = {
        "world": world,
        "candidates": [c.key() for c in candidates],
        "measured": {
            k: {"n": n, "samples_per_sec": round(mean, 3)}
            for k, (n, mean) in sorted(history.items())
        },
        "current": current,
        "probes_used": probes_used,
        "pinned": pinned or None,
        "bad": sorted(bad) or None,
    }
    if pinned:
        # An operator pin deliberately BYPASSES the policy's candidate
        # pruning (that is what an override is for) — only fundamental
        # validity is checked: the shape must factorize this world, and
        # sp/ep stay job-structural. Permissive bounds express that.
        try:
            spec = MeshSpec.parse(pinned)
            problems = validate_shape(
                spec, world,
                MeshConstraints(max_tp=world, max_fsdp=world, max_pp=world))
        except ValueError as e:
            problems = [str(e)]
        if not problems:
            inputs["reason"] = "pinned"
            return MeshSpec.parse(pinned).key(), inputs
        inputs["pin_rejected"] = problems
        log.warning("pinned mesh shape %r invalid for world %d (%s); "
                    "falling back to the policy", pinned, world, problems)
    if not candidates:
        # No valid factorization (prime world with mandatory model axes,
        # world under the memory floor): fall back to pure DP and say so —
        # refusing to form a generation would be worse than a bad shape.
        inputs["reason"] = "no-valid-candidate-fallback-dp"
        return MeshSpec(dp=max(world, 1)).key(), inputs
    measured = {k: mean for k, (n, mean) in history.items()
                if n >= config.min_samples
                and any(c.key() == k for c in candidates)}
    cur_mean = measured.get(current or "")
    # Probe: the current shape is measured, budget remains, and some
    # candidate has never been tried — explore it (enumeration order).
    if cur_mean is not None and probes_used < config.max_probes_per_world:
        for c in candidates:
            if c.key() not in history:
                inputs["reason"] = "probe"
                inputs["probe"] = c.key()
                return c.key(), inputs
    # Hold while measuring: a just-probed (or just-restored) shape with
    # fewer than min_samples observations must get its chance on the
    # stopwatch — adopting the old measured best here would un-probe
    # every probe one formation later. Bounded by max_unmeasured_holds so
    # a shape whose workers crash before their first sample (OOM on an
    # over-sharded layout) is abandoned instead of crash-looped forever.
    cur_stats = history.get(current) if current is not None else None
    if (
        current is not None
        and any(c.key() == current for c in candidates)
        and (cur_stats is None or cur_stats[0] < config.min_samples)
        and holds < config.max_unmeasured_holds
    ):
        inputs["reason"] = "hold-measuring-current"
        inputs["holds"] = holds
        return current, inputs
    if measured:
        best_key = max(measured, key=lambda k: (measured[k], k))
        if (cur_mean is not None and best_key != current
                and measured[best_key] < config.improvement_floor * cur_mean):
            inputs["reason"] = "hold-hysteresis"
            return str(current), inputs
        inputs["reason"] = ("keep-measured-best" if best_key == current
                           else "adopt-measured-best")
        return best_key, inputs
    if current is not None and any(c.key() == current for c in candidates):
        inputs["reason"] = "keep-unmeasured-current"
        return current, inputs
    inputs["reason"] = "cold-start-widest-dp"
    return candidates[0].key(), inputs


@dataclass
class _ShapeStats:
    samples: Deque[float] = field(default_factory=lambda: deque(maxlen=16))

    def add(self, samples_per_sec: float, window: int) -> None:
        if self.samples.maxlen != window:
            self.samples = deque(self.samples, maxlen=window)
        self.samples.append(samples_per_sec)

    @property
    def mean(self) -> float:
        return sum(self.samples) / len(self.samples) if self.samples else 0.0


class MeshShapePolicy:
    """Stateful wrapper around :func:`mesh_shape_decision` — the object
    the master's rendezvous injects as its ``mesh_select`` and the
    simulator replays. Holds per-(world, shape) throughput windows, the
    per-world probe budget, and the cooldown stamp (as a caller-supplied
    ``now``, never a clock of its own)."""

    def __init__(self, constraints: Optional[MeshConstraints] = None,
                 config: Optional[MeshPolicyConfig] = None,
                 pinned: str = ""):
        self.constraints = constraints or MeshConstraints()
        self.config = config or MeshPolicyConfig()
        self.pinned = pinned
        self._history: Dict[Tuple[int, str], _ShapeStats] = {}
        self._current: Dict[int, str] = {}
        self._probes: Dict[int, int] = {}
        #: consecutive formations that HELD an under-measured current
        #: shape (crash-loop escape counter), per world
        self._holds: Dict[int, int] = {}
        #: shapes abandoned unmeasured after exhausting the hold budget
        #: (crash-loopers) — never probed or adopted again, per world
        self._bad: Dict[int, set] = {}
        self._last_reshape_t: float = float("-inf")
        #: decision inputs of the most recent decide() — the WAL payload
        self.last_decision: Dict[str, object] = {}

    # ------------------------------------------------------------- intake
    def observe(self, world: int, shape_key: str,
                samples_per_sec: float) -> None:
        """One throughput observation for (world, shape). The caller
        dedupes by step/generation — this object just windows."""
        if not shape_key or samples_per_sec <= 0 or world < 1:
            return
        st = self._history.setdefault((world, shape_key), _ShapeStats())
        st.add(float(samples_per_sec), self.config.window)

    # ----------------------------------------------------------- decision
    def _world_history(self, world: int) -> Dict[str, Tuple[int, float]]:
        return {
            k: (len(st.samples), st.mean)
            for (w, k), st in self._history.items() if w == world
        }

    def decide(self, world: int) -> Tuple[str, Dict[str, object]]:
        """The rendezvous' ``mesh_select`` hook: shape key + decision
        inputs for a generation forming over ``world`` chips."""
        candidates = enumerate_shapes(world, self.constraints)
        holds_before = self._holds.get(world, 0)
        cur_before = self._current.get(world)
        history = self._world_history(world)
        chosen, inputs = mesh_shape_decision(
            candidates, history,
            cur_before, self._probes.get(world, 0),
            self.config, pinned=self.pinned, world=world,
            holds=holds_before,
            bad=frozenset(self._bad.get(world, ())),
        )
        if inputs.get("reason") == "probe":
            self._probes[world] = self._probes.get(world, 0) + 1
        if inputs.get("reason") == "hold-measuring-current":
            # Only a formation where the held shape produced ZERO samples
            # counts toward the crash-loop escape: a re-formation caused
            # by unrelated member churn while a healthy shape is still
            # warming up (>=1 sample proves its workers step) must not
            # walk a perfectly good factorization into the blacklist.
            if history.get(cur_before, (0, 0.0))[0] == 0:
                self._holds[world] = holds_before + 1
            else:
                self._holds[world] = 0
        else:
            self._holds[world] = 0
            if (
                cur_before is not None and chosen != cur_before
                and holds_before >= self.config.max_unmeasured_holds
                and history.get(cur_before, (0, 0.0))[0] == 0
            ):
                # The hold budget ran out on a shape that never produced
                # a sample: its workers crash before stepping. Remember
                # it as bad — re-probing it would just crash-loop again.
                self._bad.setdefault(world, set()).add(cur_before)
                inputs["abandoned"] = cur_before
                log.warning(
                    "mesh shape %s at world %d abandoned unmeasured after "
                    "%d held formations; blacklisting it", cur_before,
                    world, holds_before)
        self._current[world] = chosen
        self.last_decision = inputs
        return chosen, inputs

    def want_reshape(self, world: int, now: float) -> bool:
        """Should the master initiate a planned reshape purely to change
        the mesh shape? True when a decide() at this instant would pick a
        different shape than the running one (a probe, or adopting a
        measured-better candidate), respecting the cooldown. Pure given
        ``now``; the caller stamps :meth:`note_reshape` when it actually
        acts."""
        if self.pinned or world < 1:
            return False
        current = self._current.get(world)
        if current is None:
            return False
        if now - self._last_reshape_t < self.config.probe_cooldown_s:
            return False
        candidates = enumerate_shapes(world, self.constraints)
        chosen, inputs = mesh_shape_decision(
            candidates, self._world_history(world), current,
            self._probes.get(world, 0), self.config,
            pinned=self.pinned, world=world,
            holds=self._holds.get(world, 0),
            bad=frozenset(self._bad.get(world, ())),
        )
        return chosen != current

    def note_reshape(self, now: float) -> None:
        self._last_reshape_t = now

    # ------------------------------------------------------------- status
    def status(self) -> Dict[str, object]:
        worlds: Dict[str, Dict[str, object]] = {}
        for (w, k), st in sorted(self._history.items()):
            worlds.setdefault(str(w), {})[k] = {
                "n": len(st.samples),
                "samples_per_sec": round(st.mean, 3),
            }
        return {
            "pinned": self.pinned or None,
            "current": {str(w): k for w, k in sorted(self._current.items())},
            "probes": {str(w): n for w, n in sorted(self._probes.items())},
            "bad": {str(w): sorted(b)
                    for w, b in sorted(self._bad.items()) if b},
            "history": worlds,
        }


def policy_from_job_config(cfg) -> Optional[MeshShapePolicy]:
    """Build the policy the job config asks for (None = static mesh, the
    pre-PR-12 behavior). Activation: a ``mesh_policy`` mapping in
    job.json, e.g. ``{"constraints": {"max_tp": 2, "max_fsdp": 2},
    "pin": "", "min_samples": 3}``. The EASYDL_MESH_PIN knob (read by the
    caller, passed as ``pin``) overrides the config pin."""
    doc = dict(cfg or {}).get("mesh_policy")
    if not isinstance(doc, dict):
        return None
    return MeshShapePolicy(
        constraints=MeshConstraints.from_dict(doc.get("constraints", {})),
        config=MeshPolicyConfig.from_dict(doc),
        pinned=str(doc.get("pin", "") or ""),
    )

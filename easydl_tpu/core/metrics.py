"""Step metrics: collection, aggregation, and export toward Brain.

The reference requires performance monitoring to drive Brain's re-plans
(README.md:21-23, docs/design/elastic-training-operator.md:110-112) but
specifies no pipeline. Here the trainer records per-step wall time +
throughput, keeps windowed aggregates, and any reporter (gRPC to Brain, logs)
consumes :class:`StepRecord` snapshots.
"""

from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

from easydl_tpu.obs import get_registry
from easydl_tpu.proto import easydl_pb2 as pb


@dataclass
class StepRecord:
    step: int
    loss: float
    step_time_s: float
    samples_per_sec: float
    world_size: int
    timestamp: float = field(default_factory=time.time)
    extras: Dict[str, float] = field(default_factory=dict)

    def to_proto(self, job_name: str) -> pb.StepMetrics:
        return pb.StepMetrics(
            job_name=job_name,
            step=self.step,
            step_time_s=self.step_time_s,
            samples_per_sec=self.samples_per_sec,
            world_size=self.world_size,
            loss=self.loss,
            timestamp=self.timestamp,
        )

    @property
    def samples_per_sec_per_chip(self) -> float:
        return self.samples_per_sec / max(self.world_size, 1)


Reporter = Callable[[StepRecord], None]


class MetricsRecorder:
    """Records steps, maintains a sliding window, fans out to reporters.

    The first ``warmup`` steps are excluded from window statistics (they
    include XLA compilation).
    """

    def __init__(
        self,
        global_batch: int,
        world_size: int,
        window: int = 50,
        warmup: int = 1,
    ):
        self.global_batch = global_batch
        self.world_size = world_size
        self.warmup = warmup
        self._window: Deque[StepRecord] = collections.deque(maxlen=window)
        self._reporters: List[Reporter] = []
        self._count = 0
        self._last_t: Optional[float] = None
        # Telemetry bridge: every recorded step also lands in the process
        # registry, so any process running a train loop (zoo runner,
        # evaluator warm-up, benchmarks) exposes live throughput the moment
        # an exporter is attached — no extra reporter wiring.
        reg = get_registry()
        self._g_step = reg.gauge(
            "easydl_train_step", "Latest recorded training step.")
        self._g_rate = reg.gauge(
            "easydl_train_samples_per_sec", "Windowed mean global training "
            "throughput.")

    def add_reporter(self, reporter: Reporter) -> None:
        self._reporters.append(reporter)

    def start_step(self) -> None:
        self._last_t = time.perf_counter()

    def end_step(self, step: int, loss: float, **extras: float) -> StepRecord:
        now = time.perf_counter()
        dt = (now - self._last_t) if self._last_t is not None else 0.0
        self._last_t = now
        rec = StepRecord(
            step=step,
            loss=loss,
            step_time_s=dt,
            samples_per_sec=self.global_batch / dt if dt > 0 else 0.0,
            world_size=self.world_size,
            extras=extras,
        )
        self._count += 1
        if self._count > self.warmup:
            self._window.append(rec)
        self._g_step.set(step)
        self._g_rate.set(self.mean_samples_per_sec() or rec.samples_per_sec)
        for r in self._reporters:
            r(rec)
        return rec

    # ---------------------------------------------------------------- windows
    def mean_step_time(self) -> float:
        if not self._window:
            return 0.0
        return sum(r.step_time_s for r in self._window) / len(self._window)

    def mean_samples_per_sec(self) -> float:
        if not self._window:
            return 0.0
        return sum(r.samples_per_sec for r in self._window) / len(self._window)

    def mean_samples_per_sec_per_chip(self) -> float:
        return self.mean_samples_per_sec() / max(self.world_size, 1)

    def summary(self) -> Dict[str, float]:
        return {
            "steps": float(self._count),
            "mean_step_time_s": self.mean_step_time(),
            "samples_per_sec": self.mean_samples_per_sec(),
            "samples_per_sec_per_chip": self.mean_samples_per_sec_per_chip(),
        }

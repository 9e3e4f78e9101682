"""Structured per-component logging.

The reference has no logging subsystem (lint-only CI); Brain's inputs imply one
(README.md:21-23 performance monitoring). Every easydl_tpu process logs through
here so component/role/host are always attached.
"""

from __future__ import annotations

import logging
import sys
import time
from easydl_tpu.utils.env import knob_str
from typing import Optional

_FORMAT = "%(asctime)s %(levelname).1s %(name)s] %(message)s"
_configured = False


def _configure_root() -> None:
    global _configured
    if _configured:
        return
    handler = logging.StreamHandler(sys.stderr)
    handler.setFormatter(logging.Formatter(_FORMAT))
    root = logging.getLogger("easydl_tpu")
    root.addHandler(handler)
    level = knob_str("EASYDL_LOG_LEVEL").upper()
    if level not in ("DEBUG", "INFO", "WARNING", "ERROR", "CRITICAL"):
        level = "INFO"
    root.setLevel(level)
    root.propagate = False
    _configured = True


def get_logger(component: str, role: Optional[str] = None) -> logging.Logger:
    """Logger named ``easydl_tpu.<component>[.<role>]``."""
    _configure_root()
    name = f"easydl_tpu.{component}" + (f".{role}" if role else "")
    return logging.getLogger(name)


_logged_once: set = set()


def log_once(logger: logging.Logger, message: str) -> None:
    """Log ``message`` at INFO the first time this process says it.

    For choices made at trace time (which attention path, which device):
    a jitted function retraces, the statement stays one line, and a run's
    log can be checked for it."""
    if (logger.name, message) not in _logged_once:
        _logged_once.add((logger.name, message))
        logger.info("%s", message)


class StepTimer:
    """Cheap wall-clock step timer used by the trainer's metrics loop."""

    def __init__(self) -> None:
        self._t0 = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt = now - self._t0
        self._t0 = now
        return dt

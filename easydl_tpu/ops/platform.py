"""The one place the ops ask what they run on: the Pallas kernels are
compiled on a TPU and nowhere else (``ops/attention.py`` takes the XLA
reference path off it, ``ops/moe.py`` the Pallas interpreter). A compile for
a DESCRIBED chip in a process whose ``jax.devices()`` is the CPU patches
:func:`on_tpu` (``tests/conftest.py described_tpu``,
``scripts/rehearse_tpu_compile.py``) and, to state the room a described chip
has, :func:`memory_stats` (the script; a test that injects a limit)."""

from typing import Dict, Optional

import jax


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def memory_stats(device) -> Optional[Dict[str, int]]:
    """``device.memory_stats()``: the allocator's ``bytes_limit`` and
    ``bytes_in_use`` on a TPU, None where the platform keeps none (the CPU)
    or the device is only described."""
    try:
        return device.memory_stats()
    except jax.errors.JaxRuntimeError:  # a described device has no client
        return None

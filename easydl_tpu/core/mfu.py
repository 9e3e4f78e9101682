"""Model-FLOP-utilisation: the program's ONE definition, for the live
fleet.

MFU = achieved model FLOP/s / the chip's peak dense FLOP/s. The numerator
uses the PaLM appendix-B accounting and follows the model's DESCRIPTION,
not its parameter count alone: a bundle's ``flops_per_sample_hint`` is
``TransformerConfig.train_flops_per_token`` — 6 a matrix parameter, the
scores of each attention layer, the scan of each Mamba-2 layer, and for a
looped stack (``loops`` passes over the same parameters) layers, scores and
head once a PASS — of which :func:`model_flops_per_token` is the
all-attention, one-pass case. The
denominator comes from :func:`peak_flops_per_chip`. The elastic worker
stamps ``mfu`` into its step-metrics records with THESE functions, the
agent surfaces it live as the ``easydl_worker_mfu`` gauge, and the Brain's
mesh-shape policy reads the throughput it normalises. The benchmark has
its own count and its own table of peaks under ``benchmark/lib/``, by
design independent of the program: the instrument imports nothing it
measures.

The denominator is never a guess: a ``device_kind`` the table does not
know raises (a CPU has no peak to normalise by, and a new chip's peak must
be stated, not assumed), and ``EASYDL_CHIP_PEAK_TFLOPS`` overrides the table
outright (the knob for chips the table has never heard of, declared in
utils/env.py).
"""

from __future__ import annotations

from typing import Dict

from easydl_tpu.utils.env import knob_raw
from easydl_tpu.utils.logging import get_logger

log = get_logger("core", "mfu")

#: Peak dense bf16 FLOP/s per chip by device kind (public Cloud TPU specs).
PEAK_FLOPS: Dict[str, float] = {
    "v6": 918e12,   # Trillium
    "v5p": 459e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def peak_flops_per_chip(device_kind: str) -> float:
    """Peak dense FLOP/s for ``device_kind``.

    Resolution order: the ``EASYDL_CHIP_PEAK_TFLOPS`` knob (an explicit
    operator statement — wins even for known chips, e.g. to model an
    fp8-rated peak), then the spec table. A kind neither knows raises
    ``ValueError``: an MFU normalised by an assumed denominator is not a
    measurement."""
    override = knob_raw("EASYDL_CHIP_PEAK_TFLOPS")
    if override:
        try:
            return float(override) * 1e12
        except ValueError:
            log.warning(
                "EASYDL_CHIP_PEAK_TFLOPS=%r is not a number; ignoring the "
                "override", override)
    kind = (device_kind or "").lower()
    for key, val in PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s known for device kind {device_kind!r}: add it to "
        "core/mfu.py PEAK_FLOPS or state it with EASYDL_CHIP_PEAK_TFLOPS")


def model_flops_per_token(n_params: int, n_layers: int, d_model: int,
                          seq_len: int) -> float:
    """Training FLOPs per token: 6N for the parameter matmuls (fwd+bwd)
    plus 12·L·d·s for the attention score/context matmuls (PaLM appendix B
    accounting — the standard MFU numerator)."""
    return 6.0 * n_params + 12.0 * n_layers * d_model * seq_len


def mfu(achieved_flops_per_sec: float, n_chips: int,
        device_kind: str) -> float:
    """Fleet MFU: achieved model FLOP/s over ``n_chips`` x peak."""
    denom = max(n_chips, 1) * peak_flops_per_chip(device_kind)
    return achieved_flops_per_sec / denom if denom > 0 else 0.0

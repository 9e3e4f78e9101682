"""Published peaks of the chips this benchmark may run on, one table.

Keyed by the exact ``device_kind`` jax prints for the chip. A kind that is
not here is an error, never a default, and no environment variable replaces
a row (``core/mfu.py``'s ``EASYDL_CHIP_PEAK_TFLOPS`` does not reach here).

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect, per chip.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak(device_kind: str, what: str) -> float:
    """``PEAKS[device_kind][what]``; an unknown kind raises ``KeyError``
    naming the kinds the table has."""
    try:
        row = PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; "
            f"benchmark/lib/peaks.py knows {sorted(PEAKS)}") from None
    return row[what]

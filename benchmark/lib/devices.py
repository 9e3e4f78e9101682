"""Which device a run is on, as jax reports it — and no run without it."""

from __future__ import annotations

from typing import Any, Dict, List


class NoDevice(SystemExit):
    """The accelerator the cell asks for is not there: the run ends with a
    non-zero code and prints no result."""

    def __init__(self, message: str):
        super().__init__(f"benchmark: {message}. No result printed.")


def require(platform: str, chips: int) -> List[Any]:
    """The first ``chips`` jax devices, which must be of ``platform``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoDevice(
            f"this cell measures on a {platform}; jax found "
            f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoDevice(f"this cell needs {chips} {platform} chip(s); jax "
                       f"found {len(devices)}")
    return devices[:chips]


def describe(devices: List[Any]) -> Dict[str, Any]:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count()}


def peak_bytes_in_use(devices: List[Any]) -> int:
    """Largest ``peak_bytes_in_use`` over ``devices``; 0 where the backend
    keeps no memory statistics (the CPU)."""
    return max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices), default=0)

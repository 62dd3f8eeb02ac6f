"""The chips a run stands on: a TPU or nothing, and how much of it."""
from __future__ import annotations

import jax


class NoChip(RuntimeError):
    pass


def require(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"this benchmark measures a TPU; JAX found "
                     f"{devs[0].platform!r} devices only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` on the fullest chip (0 where unreported).
    On this runtime the counter follows live arrays and misses a
    program's temporaries."""
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dev in devs]
    return int(max(peaks))

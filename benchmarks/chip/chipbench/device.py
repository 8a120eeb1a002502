"""The chip: find it, name it, and know its peaks.

A run needs a TPU with at least as many chips as its cell asks for.
Without one it fails; it never falls back to the CPU.
"""
from __future__ import annotations

from typing import Dict

# Published peaks of one chip, keyed by ``device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}
PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def peaks(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {kind!r}; known: "
                       f"{sorted(PEAKS)} ({PEAKS_SOURCE})")
    return PEAKS[kind]


def require_chips(n: int):
    """The first ``n`` TPU devices, or ``NoChip``."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:            # no backend could start
        raise NoChip(f"JAX found no device: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"needs {n} chips, JAX found {len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:n]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def describe(devices) -> Dict[str, object]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}

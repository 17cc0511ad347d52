"""Published peaks of the accelerators the benchmark runs on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

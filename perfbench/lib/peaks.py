"""Published per-chip peaks, keyed by JAX's ``device_kind``. A device that
is not in the table is an error, never a default.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s dense bf16,
16 GB HBM at 819 GB/s per chip."""

from __future__ import annotations

from typing import Dict

DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819.0e9, "bf16_flops_per_s": 197.0e12,
                    "hbm_bytes": 16.0e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"(have {sorted(DEVICE_PEAKS)}): add them with their "
                       f"source")
    return DEVICE_PEAKS[device_kind]

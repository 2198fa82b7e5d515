"""Published peaks of each accelerator the benchmark runs on, keyed by
``jax.Device.device_kind``. A device that is not in the table is an error,
not a default.

TPU v5e, one chip: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}

SOURCE = "Google Cloud documentation, TPU v5e (cloud.google.com/tpu/docs/v5e)"


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

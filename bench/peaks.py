"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
v5e chip has 16 GB of HBM at 819 GB/s and 197 TFLOP/s in bfloat16.  JAX
reports its ``device_kind`` as "TPU v5 lite".  A chip that is not in the
table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None

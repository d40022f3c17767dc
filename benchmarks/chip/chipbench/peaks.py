"""Published peaks of each chip the benchmark may run on, by
``device_kind`` as JAX reports it.  A chip that is not here is an error,
never a default."""

from __future__ import annotations

from typing import Dict

#: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM2 at 819 GB/s
_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

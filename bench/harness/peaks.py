"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not listed is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""

from __future__ import annotations

__all__ = ["PEAKS", "peaks_for"]

_V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to bench/harness/peaks.py"
                       ) from None

"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not in the table is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e documentation"}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises ``KeyError`` when the
    kind is unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s.  A device kind that is not listed is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, key: str) -> float:
    """The published ``key`` of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind][key]
    except KeyError:
        raise KeyError(f"no published {key!r} for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

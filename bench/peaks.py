"""Published peaks of each chip the benchmark runs on, keyed by JAX's
``device_kind``.  Source: Google Cloud documentation, "TPU v5e" (per chip:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s)."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(LookupError):
    pass


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None

"""Published peaks per chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s
of chip-to-chip interconnect per chip. The profiler's own device plane says
202.7 TFLOP/s and 819.16 GB/s for the same chip; the published figures are
the yardstick here.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"to bench/peaks.py (known: {sorted(PEAKS)})") from None

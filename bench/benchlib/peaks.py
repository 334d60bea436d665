"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture, v5e chip specifications): per chip 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s inter-chip
interconnect.  A kind that is not in the table is an error, never a
default.
"""
from __future__ import annotations

SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
          "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip")

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device_kind {device_kind!r} is not in the peaks "
                       f"table (known: {sorted(PEAKS)})") from None

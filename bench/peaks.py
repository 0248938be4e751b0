"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip
interconnect (copied from the program's ``analysis/roofline.py:PEAKS``).
A kind missing here is an error, never a default.
"""
from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add them to bench/peaks.py "
                         f"with their source") from None

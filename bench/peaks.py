"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud TPU documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect. A kind missing from
the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known "
            f"kinds: {sorted(PEAKS)}") from None

"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source for "TPU v5 lite" (v5e): Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s. Copied from the program's
``launch/hlo_analysis.PEAKS`` so that no change to the program moves the
yardstick. A kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None

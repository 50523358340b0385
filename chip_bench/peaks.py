"""Published peaks of each accelerator the benchmark accepts, keyed by
JAX's ``device_kind``. A device that is not here is an error: rooflines
and utilizations are never computed against a guessed peak."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table entry for ``device_kind``; KeyError names the kinds
    that are known."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, bytes_: float, peaks: dict) -> tuple:
    """The least time the chip could take for ``flops`` operations and
    ``bytes_`` of memory traffic, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bytes")

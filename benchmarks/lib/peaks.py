"""Peaks of the chips the benchmark knows, keyed by the exact
``device_kind`` JAX reports.  A device that is not here is an error,
never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page:
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks known for device_kind {device_kind!r}; the "
            f"benchmark measures only {sorted(PEAKS)}") from None

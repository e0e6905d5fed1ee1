"""Published peaks per chip, keyed by JAX's ``device_kind``. A device that
is not in the table is an error, never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (system architecture table):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
#: inter-chip interconnect per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            "to bench/peaks.py with their source"
        ) from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """Roofline floor: the larger of compute time at peak and memory time
    at peak bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])

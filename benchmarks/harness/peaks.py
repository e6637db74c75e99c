"""Published peaks, keyed by ``device_kind``. A kind that is not in the
table is an error, never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" system architecture page:
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r}; add it to "
            "benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind][key]

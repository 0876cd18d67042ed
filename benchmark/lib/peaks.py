"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip. A device
that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
    },
}


def peak(device_kind: str, what: str) -> float:
    try:
        return float(PEAKS[device_kind][what])
    except KeyError:
        raise ValueError(
            f"no published {what} for device_kind {device_kind!r}: add the"
            " device to benchmark/lib/peaks.py with its source"
        ) from None

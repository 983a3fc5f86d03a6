"""Published peaks of the accelerators the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
one v5e chip has 16 GB of HBM at 819 GB/s and 197 TFLOP/s in bfloat16.
JAX reports that chip's ``device_kind`` as "TPU v5 lite".

A kind that is not in the table is an error, never a default: a share of
a peak taken against the wrong chip's peak is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add the "
            "chip to bench/lib/peaks.py with its source"
        ) from None

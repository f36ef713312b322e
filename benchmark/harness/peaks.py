"""Published peaks of one chip, keyed by the `device_kind` JAX reports.

Copied from `paddle_tpu/place.py` `peak_bf16_flops` (sound, incomplete)
and completed: the yardstick lives with the benchmark so that no later
PR can move it. A device that is not in the table is an error, never a
default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture: per chip
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
    # inter-chip interconnect
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise ValueError(
            f"no {what!r} peak recorded for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add it to benchmark/harness/peaks.py "
            "with its source") from None

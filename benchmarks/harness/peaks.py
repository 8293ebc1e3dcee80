"""One table of device peaks, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, never a default: a share of
a made-up peak is not a measurement.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: int           # B per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 10 ** 9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM2e at 819 GB/s per chip"),
}


class UnknownDevice(RuntimeError):
    pass


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in benchmarks/harness/"
            f"peaks.py (known: {sorted(PEAKS)}); add its published peaks "
            f"with their source before measuring on it") from None

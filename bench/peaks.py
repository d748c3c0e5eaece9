"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A chip that is not in the table is an
error, never a default."""
from __future__ import annotations

import dataclasses

SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
          "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip")


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float        # dense bf16 matrix-unit peak
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peak(flops_per_s=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}") from None

"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error: a share
of a peak is never taken against a guessed one.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  No
float32 peak is published; float32 work is held to the bf16 peak, so a
share of it is never overstated.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float        # FLOP/s, bf16 on the matrix units
    hbm_bytes: float    # bytes/s of HBM
    hbm_capacity: float  # bytes
    source: str


_V5E = Peak(flops=197e12, hbm_bytes=819e9, hbm_capacity=16e9,
            source='Google Cloud documentation, "TPU v5e"')

PEAKS = {
    "TPU v5 lite": _V5E,     # what JAX reports for a v5e chip
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

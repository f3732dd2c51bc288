"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not listed is an error:
no device is ever measured against another device's peaks."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s per chip
    int8_ops: float        # OP/s per chip
    hbm_bytes: float       # HBM bytes/s per chip
    hbm_capacity: float    # HBM bytes per chip
    source: str


# "TPU v5 lite" is what JAX reports as ``device_kind`` on a TPU v5e chip.
PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=819e9,
        hbm_capacity=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB of HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; listed: {sorted(PEAKS)}") from None

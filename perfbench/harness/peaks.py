"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A chip that is not here is an error: a
roofline or utilization against a guessed peak is no measurement.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float           # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


_V5E = Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 2**30,
             source="Google Cloud documentation, TPU v5e")

PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

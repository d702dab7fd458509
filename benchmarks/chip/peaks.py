"""Peak rates of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip. The chip
reports its kind as "TPU v5 lite". A kind that is not here is an error:
there is no default peak.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # B


_V5E = Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

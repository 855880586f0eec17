"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error: a
share of a peak is never computed against a guessed peak."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s, dense bf16 matrix units
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' (per chip)"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add its "
            f"row to perfbench/peaks.py (known: {sorted(PEAKS)})") from None

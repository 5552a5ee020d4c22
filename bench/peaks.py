"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

A device that is not listed has no peaks: ``peaks_of`` raises rather than
assume another chip's.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes: float         # bytes/s
    hbm_capacity: float      # bytes
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes=819e9, hbm_capacity=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s per chip'),
}


def peaks_of(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None

"""Per-chip peak table keyed by ``device_kind`` + roofline terms.

    compute term    = FLOPs / (chips × peak FLOP/s)
    memory term     = bytes / (chips × HBM bw)
    collective term = collective bytes / (chips × ICI link bw)

All terms are SECONDS for one step of the lowered program; the dominant term
is the roofline-predicted step time, and useful-FLOPs/dominant-term/peak is
the roofline fraction ("MFU-bound").
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator generation."""

    bf16_flops: float        # FLOP/s
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s per inter-chip link, one direction
    source: str


# Keyed by ``jax.devices()[0].device_kind``. A device missing here has no
# peaks: ``chip_peaks`` raises rather than assume another chip's.
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of interconnect per chip over 4 links
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"'),
}

# the chip the static cost models (hlo_costs, report, attribution) target
TARGET_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; raises ``KeyError`` for a chip not listed."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None


_TARGET = chip_peaks(TARGET_DEVICE_KIND)
PEAK_FLOPS_BF16 = _TARGET.bf16_flops
HBM_BW = _TARGET.hbm_bw
ICI_BW = _TARGET.ici_bw


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline-predicted step time = the dominant term (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> Dict[str, float]:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_s": self.step_s,
        }


def roofline_terms(
    flops: float,
    bytes_accessed: float,
    collective_bytes: float,
    *,
    per_device: bool = True,
    chips: int = 1,
) -> RooflineTerms:
    """Three roofline terms in seconds.

    ``per_device=True`` (our HLO numbers are post-SPMD per-device programs):
    the per-chip denominators apply directly and ``chips`` is ignored.
    """
    div = 1 if per_device else max(chips, 1)
    return RooflineTerms(
        compute_s=flops / (div * PEAK_FLOPS_BF16),
        memory_s=bytes_accessed / (div * HBM_BW),
        collective_s=collective_bytes / (div * ICI_BW),
    )


def model_flops_train(n_params: int, n_tokens: int) -> float:
    """6·N·D — the standard useful-FLOPs estimate for one training step."""
    return 6.0 * n_params * n_tokens


def model_flops_infer(n_params: int, n_tokens: int) -> float:
    """2·N·D — forward-only useful FLOPs."""
    return 2.0 * n_params * n_tokens

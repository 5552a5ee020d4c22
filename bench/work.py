"""The operations and bytes that a piece of work requires, from shapes.

The pieces here know no architecture; each architecture module
(``bench/archs/<arch>.py``) adds them up over its own blocks.

These count what the algorithm needs, never what a compiled program does,
so a change to the implementation cannot move them. A packed GEMM needs
the products of its kept lanes only: ``2·M·Kp·O`` with ``Kp = K·keep/group``,
and reads its packed weights and lane table once, its input once and
writes its output once. Causal attention over ``S`` positions needs the
``S·(S+1)/2`` query-key pairs, twice (scores and values), for every head.
"""

from __future__ import annotations

import dataclasses

from bench.peaks import Peaks

BF16 = 2
LANE = 4                 # int32 entries of the lane table


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def seconds(self, p: Peaks) -> float:
        """Least time the chip could take: the larger roofline term."""
        return max(self.flops / p.bf16_flops, self.bytes / p.hbm_bytes)

    def bound(self, p: Peaks) -> str:
        return ("compute" if self.flops / p.bf16_flops
                >= self.bytes / p.hbm_bytes else "memory")


def packed_gemm(M: int, K: int, O: int, *, keep: int, group: int,
                block: int, bias: bool = False) -> Work:
    kp = K * keep // group
    return Work(2.0 * M * kp * O,
                BF16 * kp * O + LANE * (O // block) * kp
                + BF16 * (M * K + M * O + (O if bias else 0)))


def dense_gemm(M: int, K: int, O: int, *, bias: bool = False) -> Work:
    return Work(2.0 * M * K * O,
                BF16 * (K * O + M * K + M * O + (O if bias else 0)))


def flash_prefill(S: int, heads: int, kv_heads: int, head_dim: int) -> Work:
    pairs = S * (S + 1) / 2
    return Work(4.0 * head_dim * heads * pairs,
                BF16 * (2 * S * heads * head_dim + 2 * S * kv_heads * head_dim))

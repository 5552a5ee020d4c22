"""The operations and bytes that a piece of work requires, from shapes.

These count what the algorithm needs, never what a compiled program does,
so a change to the implementation cannot move them. A packed GEMM needs
the products of its kept lanes only: ``2·M·Kp·O`` with ``Kp = K·keep/group``,
and reads its packed weights and lane table once, its input once and
writes its output once. Causal attention over ``S`` positions needs the
``S·(S+1)/2`` query-key pairs, twice (scores and values), for every head.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

from bench.peaks import Peaks
from bench.spec import Shapes

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


def gemm(s: Shapes, name: str, M: int) -> Work:
    K, O = s.gemms()[name]
    bias = s.qkv_bias and name in ("wq", "wk", "wv")
    if name in s.packed:
        return packed_gemm(M, K, O, keep=s.keep, group=s.group,
                           block=s.block, bias=bias)
    return dense_gemm(M, K, O, bias=bias)


BLOCK_GEMMS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def flash_prefill(S: int, heads: int, kv_heads: int, head_dim: int) -> Work:
    pairs = S * (S + 1) / 2
    return Work(4.0 * head_dim * heads * pairs,
                BF16 * (2 * S * heads * head_dim + 2 * S * kv_heads * head_dim))


def prefill(s: Shapes, S: int) -> Work:
    """One solo prefill of ``S`` tokens: every block at M = S, then the
    output head on the last position."""
    block = sum((gemm(s, n, S) for n in BLOCK_GEMMS), Work())
    block = block + flash_prefill(S, s.heads, s.kv_heads, s.head_dim)
    return block * s.layers + gemm(s, "lm_head", 1)


def weight_bytes(s: Shapes) -> float:
    """Bytes of every weight one decode step reads: the GEMMs as stored,
    the output head (the whole embedding table where it is tied), the
    norms and biases. The lookup of an embedding row per sequence is
    left out."""
    g = s.gemms()
    total = 0.0
    for n in BLOCK_GEMMS:
        K, O = g[n]
        if n in s.packed:
            kp = K * s.keep // s.group
            total += s.layers * (BF16 * kp * O + LANE * (O // s.block) * kp)
        else:
            total += s.layers * BF16 * K * O
    K, O = g["lm_head"]
    if "lm_head" in s.packed:
        kp = K * s.keep // s.group
        total += BF16 * kp * O + LANE * (O // s.block) * kp
    else:
        total += BF16 * K * O
    total += BF16 * s.d_model * (2 * s.layers + 1)
    if s.qkv_bias:
        total += BF16 * s.layers * (g["wq"][1] + 2 * g["wk"][1])
    return total


def kv_bytes(s: Shapes, context: int) -> float:
    """Keys and values of ``context`` positions, over all layers."""
    return BF16 * 2.0 * context * s.layers * s.kv_heads * s.head_dim


def decode_token_flops(s: Shapes, context: int) -> float:
    """One token decoded against ``context`` cached positions (itself
    included)."""
    block = sum(gemm(s, n, 1).flops for n in BLOCK_GEMMS)
    attn = 4.0 * s.head_dim * s.heads * context
    return s.layers * (block + attn) + gemm(s, "lm_head", 1).flops


def decode_step(s: Shapes, contexts: Iterable[int]) -> Work:
    """One decode step of a batch whose live rows hold ``contexts``."""
    ctx = list(contexts)
    return Work(sum(decode_token_flops(s, c) for c in ctx),
                weight_bytes(s) + sum(kv_bytes(s, c) for c in ctx)
                + BF16 * s.d_model * len(ctx))

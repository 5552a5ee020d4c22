"""Pallas TPU kernel: flash attention forward (§Perf iteration 4).

WHY: the XLA lowering of blockwise attention materializes every (cq × ck)
score tile in HBM through an elementwise chain (mask-select → subtract →
exp → layout copies). The §Perf diagnosis of phi4-mini prefill_32k showed
those tile ops are >80% of the memory term. In a Pallas kernel the tile
lives in VMEM for its whole life; HBM traffic is exactly q + k + v + out.

Grid: (batch·kv_heads·groups, S/block_q). Each program instance owns one
(block_q × hd) query tile and loops over the kv chunks of its causal/window
band with the online-softmax (m, l, acc) carry held in VMEM scratch.

VMEM budget per instance (block_q=512, block_k=512, hd=128, f32 compute):
    q tile 256 KiB + k/v tiles 2·256 KiB + score tile 1 MiB + acc 256 KiB
    ≈ 2 MiB ≪ 16 MiB VMEM — and block_q/block_k are multiples of the
    128-lane MXU dimension.

Forward-only by design: training keeps the XLA blockwise path (its backward
is generated by AD); serving/prefill — where the roofline showed the
pathology — has no backward. ``ops.flash_attention`` dispatches, ``ref.py``
holds the oracle, tests sweep shapes/dtypes/masks in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, seq_len: int, block_q: int,
            block_k: int, causal: bool, window: Optional[int],
            scale: float, f32_dot: bool):
    """One (block_q × hd) output tile; loops kv chunks in VMEM."""
    qi = pl.program_id(1)
    q = q_ref[0]                                    # (block_q, hd)
    if f32_dot:
        q = q.astype(jnp.float32)
    q = q * scale
    hd = q.shape[-1]
    n_k = seq_len // block_k

    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    def body(kj, carry):
        m, l, acc = carry
        start = pl.multiple_of(kj * block_k, block_k)
        k = k_ref[0, pl.ds(start, block_k), :]      # (block_k, hd)
        v = v_ref[0, pl.ds(start, block_k), :]
        if f32_dot:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (block_q, block_k)
        k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            ok &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(ok, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr[:, None] + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, hd), jnp.float32)

    # causal/window band: only the kv chunks that can contain unmasked keys
    if causal:
        hi = jnp.minimum(((qi + 1) * block_q + block_k - 1) // block_k, n_k)
    else:
        hi = n_k
    if window is not None:
        lo = jnp.maximum((qi * block_q - (window - 1)) // block_k, 0)
    else:
        lo = 0
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k",
                     "softmax_scale", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,                  # (B, S, H, hd)
    k: jnp.ndarray,                  # (B, S, KV, hd)
    v: jnp.ndarray,                  # (B, S, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 512,
    block_k: int = 512,
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention forward. GQA: q heads grouped onto KV heads."""
    from repro.kernels.ops import _default_interpret

    if interpret is None:
        interpret = _default_interpret()
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} not tiled by ({block_q}, {block_k})")

    # (B, S, H, hd) → (B·H, S, hd); kv repeated per group at trace level —
    # XLA propagates the broadcast into the kernel's gather, no HBM copy
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, hd)

    f32_dot = interpret and q.dtype == jnp.bfloat16

    out = pl.pallas_call(
        functools.partial(
            _kernel, seq_len=S, block_q=block_q, block_k=block_k,
            causal=causal, window=window, scale=scale, f32_dot=f32_dot,
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)

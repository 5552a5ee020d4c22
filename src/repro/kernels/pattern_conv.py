"""Pallas TPU kernel: pattern-pruned 3×3 convolution (paper §V-C on TPU).

The faithful object: a conv whose kernels keep exactly 4 of 9 taps, drawn
from a fixed pattern LIBRARY (``core.projections.canonical_patterns_3x3``)
with CHANNEL-WISE pattern assignment — all filters share channel c's pattern.
That sharing is the TPU translation of filter-kernel-reorder: instead of
reordering filters so same-pattern kernels run together on SIMD lanes (the
mobile trick), we make the pattern uniform across the filter (output) dim of
a tile, so the packed computation is one dense MXU GEMM:

    im2col-lite:  for channel c only its 4 taps are gathered
                  xg (B·H·W, 4·C)   — LRE: each input pixel read once/tap
    packed GEMM:  y = xg @ w_packed (4·C, A)  — CWS: zeros never stored

vs the dense conv's (B·H·W, 9·C) @ (9·C, A): 2.25× fewer FLOPs and weight
bytes — exactly the paper's kernel-pattern compression rate.

The tap gather (9 shifted views → select 4 per channel) is plain XLA that
fuses with upstream ops; the hot GEMM is the Pallas kernel below, tiled for
VMEM with fp32 accumulation over K chunks.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.projections import canonical_patterns_3x3
from repro.kernels.epilogue import apply_epilogue, check_activation
from repro.kernels.grids import accum_gemm_grid


def assign_channel_patterns(w4: jnp.ndarray, patterns: np.ndarray = None
                            ) -> np.ndarray:
    """Best library pattern per input channel, shared over filters.

    w4: (A, C_in, 3, 3). Returns pattern ids (C_in,). The choice maximizes
    retained energy summed over all filters — the Euclidean projection under
    the channel-shared-pattern constraint.
    """
    if patterns is None:
        patterns = canonical_patterns_3x3()
    wf = np.asarray(w4, np.float32)
    A, C, KH, KW = wf.shape
    sq = (wf ** 2).reshape(A, C, KH * KW).sum(axis=0)      # (C, 9)
    energy = sq @ patterns.T.astype(np.float32)            # (C, n_pat)
    return np.argmax(energy, axis=1).astype(np.int32)


def pack_pattern_conv(
    w4: jnp.ndarray, pat_ids: np.ndarray, patterns: np.ndarray = None
) -> Tuple[jnp.ndarray, np.ndarray]:
    """Pack (A, C, 3, 3) + channel pattern ids → (w_packed (4C, A), taps (C,4)).

    ``taps[c]`` are the flat 3×3 tap indices kept for channel c;
    ``w_packed[c*4+j, a]`` = w4[a, c, taps[c,j]//3, taps[c,j]%3].
    """
    if patterns is None:
        patterns = canonical_patterns_3x3()
    wf = np.asarray(w4, np.float32)
    A, C, KH, KW = wf.shape
    keep = int(patterns[0].sum())
    taps = np.zeros((C, keep), np.int32)
    w_packed = np.zeros((C * keep, A), wf.dtype)
    for c in range(C):
        t = np.nonzero(patterns[pat_ids[c]])[0]
        taps[c] = t
        w_packed[c * keep:(c + 1) * keep, :] = wf[:, c, t // KW, t % KW].T
    return jnp.asarray(w_packed, w4.dtype), taps


def gather_taps(x: jnp.ndarray, taps: np.ndarray) -> jnp.ndarray:
    """im2col-lite: x (B, H, W, C) → (B·H·W, keep·C) with per-channel taps.

    Built from 9 shifted views (SAME padding) then a static gather over the
    (tap, channel) axis — XLA fuses the shifts+gather with the surrounding
    graph; there is no 9·C materialization.
    """
    B, H, W, C = x.shape
    keep = taps.shape[1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    views = jnp.stack(
        [xp[:, dy:dy + H, dx:dx + W, :] for dy in range(3) for dx in range(3)],
        axis=3,
    )                                                       # (B,H,W,9,C)
    # channel-major ordering (c*keep + j) — must match pack_pattern_conv rows
    flat_idx = taps.astype(np.int32) * C + np.arange(C)[:, None]   # (C, keep)
    flat = views.reshape(B, H, W, 9 * C)
    xg = jnp.take(flat, jnp.asarray(flat_idx.reshape(-1)), axis=3)
    return xg.reshape(B * H * W, keep * C)


def _kernel(*refs, n_k: int, f32_dot: bool = False, has_bias: bool = False,
            activation=None):
    if has_bias:
        x_ref, w_ref, b_ref, o_ref = refs
    else:
        (x_ref, w_ref, o_ref), b_ref = refs, None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x, w = x_ref[...], w_ref[...]
    if f32_dot:                       # interpret-mode CPU: no bf16 DotThunk
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    if has_bias or activation is not None:
        # fused epilogue on the finished fp32 tile (k iterates fastest)
        @pl.when(k == n_k - 1)
        def _epilogue():
            o_ref[...] = apply_epilogue(
                o_ref[...], b_ref[0] if has_bias else None, activation
            )


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_a", "block_k", "interpret",
                              "activation", "grid_order")
)
def pattern_conv_gemm(
    xg: jnp.ndarray,             # (M, keep·C) gathered taps
    w_packed: jnp.ndarray,       # (keep·C, A)
    bias: Optional[jnp.ndarray] = None,     # (A,) fused-epilogue bias
    *,
    block_m: int = 256,
    block_a: int = 128,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    activation: Optional[str] = None,       # relu | silu | gelu | None
    grid_order: str = "mp",                 # outer-loop order; k innermost
) -> jnp.ndarray:
    """The packed-GEMM hot loop of the pattern conv (+ fused epilogue).

    Large-M regime knobs mirror ``column_gemm``: ``block_m`` sizes the
    multi-row output panel (conv M = B·H·W is prefill-sized by nature),
    ``block_k`` the k-panel prefetch granularity, and ``grid_order``
    whether row tiles (``mp``) or filter tiles (``pm``) run outermost —
    k always iterates fastest for the accumulate-in-place output tile.
    """
    from repro.kernels.ops import _default_interpret

    if interpret is None:
        interpret = _default_interpret()
    check_activation(activation)
    M, K = xg.shape
    K2, A = w_packed.shape
    bm = min(block_m, M)
    ba = min(block_a, A)
    bk = min(block_k, K)
    pad_m, pad_a, pad_k = (-M) % bm, (-A) % ba, (-K) % bk
    if pad_m or pad_k:
        xg = jnp.pad(xg, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_a:
        w_packed = jnp.pad(w_packed, ((0, pad_k), (0, pad_a)))
    Mp, Kp, Ap = M + pad_m, K + pad_k, A + pad_a
    n_k = Kp // bk

    needs_f32 = interpret and xg.dtype == jnp.bfloat16
    grid, im_x, im_w, im_b, im_o = accum_gemm_grid(
        grid_order, Mp // bm, Ap // ba, n_k)
    in_specs = [
        pl.BlockSpec((bm, bk), im_x),
        pl.BlockSpec((bk, ba), im_w),
    ]
    operands = [xg, w_packed]
    if bias is not None:
        if pad_a:
            bias = jnp.pad(bias, (0, pad_a))
        in_specs.append(pl.BlockSpec((1, ba), im_b))
        operands.append(bias.reshape(1, Ap))
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, f32_dot=needs_f32,
                          has_bias=bias is not None, activation=activation),
        out_shape=jax.ShapeDtypeStruct((Mp, Ap), jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, ba), im_o),
        interpret=interpret,
        name="pattern_conv_gemm",
    )(*operands)
    return out[:M, :A].astype(xg.dtype)


def pattern_conv(
    x: jnp.ndarray,              # (B, H, W, C)
    w_packed: jnp.ndarray,       # (keep·C, A)
    taps: np.ndarray,            # (C, keep)
    bias: Optional[jnp.ndarray] = None,     # (A,) fused-epilogue bias
    *,
    interpret: Optional[bool] = None,
    activation: Optional[str] = None,
) -> jnp.ndarray:
    """Pattern-pruned 3×3 conv, stride 1, SAME padding → (B, H, W, A).

    The (bias, activation) epilogue fuses into the packed GEMM: conv →
    bias → relu writes back once instead of materializing the conv output.
    """
    B, H, W, C = x.shape
    xg = gather_taps(x, taps)
    y = pattern_conv_gemm(xg, w_packed, bias, interpret=interpret,
                          activation=activation)
    return y.reshape(B, H, W, -1)

"""Pallas TPU kernel: column/connectivity-pruned GEMM.

Column pruning (paper Eqn. 15) zeroes whole columns of the GEMM weight
matrix; connectivity pruning (Eqn. 18) zeroes whole kernels, which in GEMM
view is column-GROUP pruning. Either way the pruned computation is

    y (M, P) = x[:, kept] (M, K) @ w_packed (K, P)

with the pruned columns PHYSICALLY absent (compressed weight storage). The
kernel tiles (M, P, K) over the grid, revisiting the same fp32 output tile
across the K dimension (accumulate-in-place) and streaming packed weight
tiles through VMEM — each surviving input element crosses HBM→VMEM once
per output tile (load redundancy elimination). Unlike ``pattern_gemm`` the
kept-column set is global to the layer, so the gather is hoisted OUT of the
kernel (done once by XLA, fusing with upstream producers) and the kernel
body is a pure dense MXU matmul — the fastest shape when sparsity is
column-structured.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.epilogue import apply_epilogue, check_activation
from repro.kernels.grids import accum_gemm_grid


def pack_columns(w: jnp.ndarray, *, group: int = 1
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack a column-pruned W (Q, P) → (w_packed (K, P), kept_idx (K,)).

    A column q survives if any entry in row q (of the Q axis) is nonzero.
    ``group`` asserts/derives group-aligned survival (connectivity pruning
    uses group = C·D of the conv kernel).
    """
    wf = np.asarray(w)
    alive = np.any(wf != 0, axis=1)                     # (Q,)
    if group > 1:
        blk = np.any(alive.reshape(-1, group), axis=1)
        alive = np.repeat(blk, group)
    kept = np.nonzero(alive)[0].astype(np.int32)
    return jnp.asarray(wf[kept]), jnp.asarray(kept)


def _kernel(*refs, n_k: int, interpret: bool, has_bias: bool = False,
            activation=None):
    """Accumulate one (bm × bp) fp32 output tile over K chunks.

    In interpret mode a bf16 tile is upcast (CPU DotThunk lacks
    BF16×BF16→F32); on TPU the MXU handles bf16 inputs with f32
    accumulation natively.
    The optional (bias, activation) epilogue runs on the finished fp32
    accumulator at the LAST K step — the grid is sequential with k fastest,
    so the tile is complete exactly then.
    """
    if has_bias:
        x_ref, w_ref, b_ref, o_ref = refs
    else:
        (x_ref, w_ref, o_ref), b_ref = refs, None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x, w = x_ref[...], w_ref[...]
    if interpret:
        # contract a K-major x tile, as XLA:CPU lays out the fused gather
        # of the XLA plans: the same layout sums in the same order
        x = jax.lax.optimization_barrier(x.T).T
    if interpret and x.dtype == jnp.bfloat16:
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    if has_bias or activation is not None:
        @pl.when(k == n_k - 1)
        def _epilogue():
            o_ref[...] = apply_epilogue(
                o_ref[...], b_ref[0] if has_bias else None, activation
            )


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_p", "block_k", "interpret",
                     "activation", "grid_order"),
)
def column_gemm(
    x: jnp.ndarray,              # (M, Q)
    w_packed: jnp.ndarray,       # (K, P)
    kept_idx: jnp.ndarray,       # (K,)
    bias: Optional[jnp.ndarray] = None,      # (P,) fused-epilogue bias
    *,
    block_m: int = 128,
    block_p: int = 128,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    activation: Optional[str] = None,        # relu | silu | gelu | None
    grid_order: str = "mp",                  # outer-loop order; k innermost
) -> jnp.ndarray:
    """y = act(x @ W + bias) for column-pruned W: gather kept cols, dense dot.

    Large-M regime knobs (autotuned per M-bucket by ``sparse/tune.py``):
    ``block_m`` > 128 emits multi-row output panels; ``block_k`` sets the
    k-panel prefetch granularity (smaller panels start the MXU sooner,
    larger panels amortize more grid steps); ``grid_order`` picks which of
    the (row-tile, col-tile) loops runs outermost — k always iterates
    fastest so the fp32 output tile is revisited on consecutive grid steps
    (the accumulate-in-place contract of the kernel).
    """
    from repro.kernels.ops import _default_interpret

    if interpret is None:
        interpret = _default_interpret()
    check_activation(activation)
    M, Q = x.shape
    K, P = w_packed.shape
    xg = jnp.take(x, kept_idx, axis=1)       # hoisted gather (fuses in XLA)
    bk = min(block_k, K)
    pad = (-K) % bk
    if pad:
        xg = jnp.pad(xg, ((0, 0), (0, pad)))
        w_packed = jnp.pad(w_packed, ((0, pad), (0, 0)))
        K = K + pad
    n_k = K // bk
    if M % block_m or P % block_p:
        raise ValueError(f"(M={M}, P={P}) not tiled by ({block_m}, {block_p})")

    grid, im_x, im_w, im_b, im_o = accum_gemm_grid(
        grid_order, M // block_m, P // block_p, n_k)
    in_specs = [
        pl.BlockSpec((block_m, bk), im_x),
        pl.BlockSpec((bk, block_p), im_w),
    ]
    operands = [xg, w_packed]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_p), im_b))
        operands.append(bias.reshape(1, P))
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, interpret=interpret,
                          has_bias=bias is not None, activation=activation),
        out_shape=jax.ShapeDtypeStruct((M, P), jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_p), im_o),
        interpret=interpret,
        name="column_gemm",
    )(*operands)
    return out.astype(x.dtype)

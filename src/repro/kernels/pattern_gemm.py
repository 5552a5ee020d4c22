"""Pallas TPU kernel: tile-pattern sparse GEMM (DESIGN.md §2).

The TPU adaptation of the paper's pattern-based pruning + compiler stack for
GEMM-shaped weights. The weight matrix W (Q=in, P=out) is tile-pattern
pruned (``core.projections.project_tile_pattern``): within every
(group_q=8 input lanes × block_p=128 output cols) tile, the same
``keep=4`` lanes are nonzero for all 128 output cols.

Mapping of the paper's three compiler optimizations:
  * compressed weight storage (CWS) — only the kept lanes are stored:
    ``w_packed`` is dense (Q·keep/group_q, P); zeros never touch HBM.
  * load redundancy elimination (LRE) — the x tile is loaded HBM→VMEM once
    per output tile; the per-group lane gather happens inside VMEM, so each
    input element is read from HBM exactly once per output block.
  * filter kernel reorder (FKR) — the pattern is SHARED across the 128
    output cols of a tile (the projection enforces this), which is the
    reorder/grouping that makes the packed matmul dense on the MXU.

Kernel compute: per grid cell (i, j):
    xg = gather(x[i·bm:(i+1)·bm, :], lanes[j])      # (bm, Q·keep/group_q)
    out[i, j] = xg @ w_packed[:, j·128:(j+1)·128]   # dense MXU matmul

FLOPs and HBM weight bytes both drop by group_q/keep (2× at 4-of-8).

Mosaic (the TPU kernel compiler) accepts neither a lane gather
``x[:, lanes]`` inside VMEM nor a (1, Kp) VMEM block of the lane table (the
last two block dims must be multiples of (8, 128)), and the whole table of
a wide layer overflows SMEM when scalar-prefetched (lm_head over a 151,936
vocabulary: 3.6 MB against 1 MB). So the kernel works on xᵀ: each grid
cell gets its panel's (1, 1, Kp) slice of the lane table in SMEM, a
contraction lane of x is a row of xᵀ, and the gather is Kp dynamic-offset
row copies into a VMEM scratch. That form compiles for TPU v5e at
Qwen2-1.5B widths (q, kv, up, down projections and lm_head;
``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import apply_epilogue, check_activation


def pack_tile_pattern(
    w: jnp.ndarray, *, block_p: int = 128, group_q: int = 8, keep: int = 4
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack a tile-pattern-pruned W (Q, P) → (w_packed, lane_idx).

    Returns:
      w_packed: (Q·keep/group_q, P) — kept lanes, dense (CWS)
      lane_idx: (P/block_p, Q·keep/group_q) int32 — source row of each packed
                row, per output block (the FKR grouping table)
    """
    Q, P = w.shape
    if Q % group_q or P % block_p:
        raise ValueError(f"(Q={Q}, P={P}) not tiled by ({group_q}, {block_p})")
    ng, nb = Q // group_q, P // block_p
    wf = np.asarray(w, np.float32)
    energy = (wf ** 2).reshape(ng, group_q, nb, block_p).sum(axis=3)  # (ng,g,nb)
    w_packed = np.zeros((ng * keep, P), wf.dtype)
    lane_idx = np.zeros((nb, ng * keep), np.int32)
    for j in range(nb):
        for g in range(ng):
            lanes = np.sort(np.argsort(-energy[g, :, j])[:keep])
            rows = g * group_q + lanes
            lane_idx[j, g * keep:(g + 1) * keep] = rows
            w_packed[g * keep:(g + 1) * keep, j * block_p:(j + 1) * block_p] = (
                wf[rows, j * block_p:(j + 1) * block_p]
            )
    return (jnp.asarray(w_packed, w.dtype), jnp.asarray(lane_idx))


def pack_tile_pattern_blocked(
    w: jnp.ndarray, *, block_p: int = 128, group_q: int = 8, keep: int = 4
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack into the BLOCKED dispatch layout: (nb, Kp, block_p).

    Same contents as ``pack_tile_pattern`` but with the per-output-block
    weight panel contiguous — the layout both execution paths want:
      * the Pallas kernel DMAs exactly panel j per grid column (no strided
        HBM reads across P);
      * the small-M decode fast path runs one batched dot over the nb axis
        with no per-call transpose.
    Chosen once at pack time (``sparse.registry``), not per call.
    """
    wp, lane_idx = pack_tile_pattern(
        w, block_p=block_p, group_q=group_q, keep=keep
    )
    Kp, P = wp.shape
    nb = P // block_p
    wpb = np.ascontiguousarray(
        np.asarray(wp).reshape(Kp, nb, block_p).transpose(1, 0, 2))
    return jnp.asarray(wpb), lane_idx


# Mosaic loads one row at a dynamic sublane offset only from a 32-bit
# buffer, so the row-tile is staged as float32 (exact for bf16 inputs)
_STAGE_DTYPE = jnp.float32


def _rows_per_step(kp: int) -> int:
    """Gather rows copied per loop step (a static unroll that divides Kp)."""
    for u in (8, 4, 2):
        if kp % u == 0:
            return u
    return 1


def _kernel(lane_ref, xt_ref, w_ref, *refs, kp: int, interpret: bool,
            has_bias: bool, activation):
    """One (bm × block_p) output tile: SMEM-indexed row gather + MXU matmul.

    ``lane_ref`` is this panel's (1, 1, Kp) row of the lane table, in
    SMEM. ``xt_ref`` is the float32 row-tile of xᵀ (Q, bm) in VMEM: a
    contraction lane of x is a ROW of xᵀ, so the per-panel gather is Kp
    dynamic-offset row copies into the (Kp, bm) VMEM scratch, followed by
    one MXU matmul of its transpose against the (Kp, block_p) panel.

    In interpret mode a bf16 weight is upcast — the CPU backend's DotThunk
    lacks BF16×BF16→F32; on TPU the MXU takes bf16 inputs with f32
    accumulation via ``preferred_element_type``.
    """
    if has_bias:
        b_ref, o_ref, g_ref = refs
    else:
        (o_ref, g_ref), b_ref = refs, None
    unroll = _rows_per_step(kp)
    f32_dot = interpret and w_ref.dtype == jnp.bfloat16

    def copy_rows(step, carry):
        base = pl.multiple_of(step * unroll, unroll)
        for t in range(unroll):
            src = lane_ref[0, 0, base + t]
            g_ref[pl.ds(base + t, 1), :] = xt_ref[pl.ds(src, 1), :]
        return carry

    jax.lax.fori_loop(0, kp // unroll, copy_rows, 0)
    w = w_ref[0]                                   # (Kp, block_p)
    g = g_ref[...]                                 # (Kp, bm) float32
    if interpret:
        # materialize gᵀ: XLA:CPU would otherwise fold the transpose into
        # the dot and sum in another order than the row-major gather plans
        g = jax.lax.optimization_barrier(g.T).T
    if f32_dot:
        w = w.astype(jnp.float32)
    else:
        g = g.astype(w.dtype)          # exact while x has w's dtype
    acc = jnp.dot(g.T, w, preferred_element_type=jnp.float32)
    acc = apply_epilogue(acc, b_ref[0] if has_bias else None, activation)
    o_ref[...] = acc.astype(o_ref.dtype)


def _vmem_limit(Q: int, Kp: int, bm: int, bp: int, w_bytes: int,
                o_bytes: int) -> int:
    """Scoped-VMEM request: double-buffered blocks + the gather scratch."""
    stage = jnp.dtype(_STAGE_DTYPE).itemsize
    need = (2 * Q * bm * stage + Kp * bm * stage
            + 2 * Kp * bp * w_bytes + 2 * bm * bp * (o_bytes + 4))
    return int(min(max(need + need // 4 + (4 << 20), 32 << 20), 100 << 20))


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_p", "interpret", "activation",
                              "grid_order")
)
def pattern_gemm(
    x: jnp.ndarray,               # (M, Q)
    w_packed: jnp.ndarray,        # (Kp, P) flat or (nb, Kp, block_p) blocked
    lane_idx: jnp.ndarray,        # (P/block_p, Kp)
    bias: Optional[jnp.ndarray] = None,       # (P,) fused-epilogue bias
    *,
    block_m: int = 128,
    block_p: int = 128,
    interpret: Optional[bool] = None,
    activation: Optional[str] = None,         # relu | silu | gelu | None
    grid_order: str = "mp",                   # see below
) -> jnp.ndarray:
    """y = act(x @ W + bias) for tile-pattern sparse W, packed representation.

    Accepts either weight layout: the flat (Kp, P) of ``pack_tile_pattern``
    (re-blocked here) or the blocked (nb, Kp, block_p) dispatch layout
    (``pack_tile_pattern_blocked``) — blocked infers ``block_p`` from the
    panel shape.

    ``block_m`` sets the rows per output panel; ``grid_order`` picks which
    operand stays VMEM-resident across the inner loop:

      mp — output-panel index fastest: the xᵀ row-tile is loaded once and
           all nb weight panels stream past it (LRE over panels);
      pm — row-tile index fastest: one weight panel is loaded once and
           all M/block_m row tiles stream past it (weight-resident).

    The autotuner (``sparse/tune.py``) picks (block_m, grid_order) per
    M-bucket; the winner ships in the PackedTensor's meta.
    """
    from repro.kernels.ops import _default_interpret

    if interpret is None:
        interpret = _default_interpret()
    check_activation(activation)
    M, Q = x.shape
    if w_packed.ndim == 2:
        Kp, P = w_packed.shape
        w_packed = w_packed.reshape(Kp, P // block_p, block_p).transpose(
            1, 0, 2)
    nb, Kp, block_p = w_packed.shape
    P = nb * block_p
    if lane_idx.shape != (nb, Kp):
        raise ValueError(f"lane_idx {lane_idx.shape} != {(nb, Kp)}")
    if M % block_m:
        raise ValueError(f"M={M} % block_m={block_m}")
    if grid_order not in ("mp", "pm"):
        raise ValueError(f"grid_order {grid_order!r} not in ('mp', 'pm')")

    if grid_order == "mp":                       # panel index j fastest
        grid = (M // block_m, nb)
        im_w = lambda i, j: (j, 0, 0)
        im_x = lambda i, j: (0, i)
        im_b = lambda i, j: (0, j)
        im_o = lambda i, j: (i, j)
    else:                                        # row-tile index i fastest
        grid = (nb, M // block_m)
        im_w = lambda j, i: (j, 0, 0)
        im_x = lambda j, i: (0, i)
        im_b = lambda j, i: (0, j)
        im_o = lambda j, i: (i, j)
    in_specs = [
        pl.BlockSpec((1, 1, Kp), im_w, memory_space=pltpu.SMEM),  # lanes
        pl.BlockSpec((Q, block_m), im_x),                     # xᵀ row-tile
        pl.BlockSpec((1, Kp, block_p), im_w),                 # weight panel
    ]
    operands = [lane_idx.astype(jnp.int32).reshape(nb, 1, Kp),
                x.T.astype(_STAGE_DTYPE), w_packed]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_p), im_b))
        operands.append(bias.reshape(1, P))
    return pl.pallas_call(
        functools.partial(
            _kernel, kp=Kp, interpret=interpret,
            has_bias=bias is not None, activation=activation),
        out_shape=jax.ShapeDtypeStruct((M, P), x.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_p), im_o),
        scratch_shapes=[pltpu.VMEM((Kp, block_m), _STAGE_DTYPE)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            Q, Kp, block_m, block_p, w_packed.dtype.itemsize,
            x.dtype.itemsize)),
        interpret=interpret,
        name="pattern_gemm",
    )(*operands)

"""Pieces of the plain references, and the comparison the check makes.

Each architecture module (``bench/archs/<arch>.py``) writes its reference
forward pass, ``logits_at``, in ``jax.numpy`` at ``highest`` matmul
precision from these pieces. A reference imports nothing of the program:
it reads the weights that its architecture module made from the seed, one
layer at a time, and takes the token sequence a request was served.

``fp8=True`` is the control: every weight GEMM computed from operands
rounded to float8 e4m3 (per-tensor scale), the step below the bfloat16 the
configurations state.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(a: jnp.ndarray) -> jnp.ndarray:
    """``a`` rounded to float8 e4m3 under a per-tensor scale, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(F8).astype(jnp.float32) * s


def mm(a, w, fp8: bool):
    """``a @ w`` in float32; with ``fp8``, from operands rounded to float8."""
    w = w.astype(jnp.float32)
    if fp8:
        a, w = _q8(a), _q8(w)
    return a @ w


def rms(x, scale, eps):
    """RMSNorm of the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotary positions on half-split head vectors: x (T, heads, hd)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def served_gaps(arch, s, params, prompt: Sequence[int],
                served: Sequence[int], *, pad_to: int) -> List[float]:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the reference's
    argmax); ``arch`` is the configuration's architecture module."""
    toks = list(prompt) + list(served)
    rows = [len(prompt) - 1 + j for j in range(len(served))]
    ref = arch.logits_at(s, params, toks, rows, pad_to=pad_to)
    idx = np.asarray(served)
    return list(ref.max(-1) - ref[np.arange(len(idx)), idx])


def control_gaps(arch, s, params, prompt: Sequence[int],
                 served: Sequence[int], *, pad_to: int) -> List[float]:
    """The control at the same positions: the gap, in the float32
    reference, of the token that the fp8 reference puts first."""
    toks = list(prompt) + list(served)
    rows = [len(prompt) - 1 + j for j in range(len(served))]
    ref = arch.logits_at(s, params, toks, rows, pad_to=pad_to)
    low = arch.logits_at(s, params, toks, rows, pad_to=pad_to, fp8=True)
    pick = low.argmax(-1)
    return list(ref.max(-1) - ref[np.arange(len(pick)), pick])

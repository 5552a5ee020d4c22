"""Plain reference of the served transformer, in float32.

A straightforward forward pass of the published architecture (Qwen2:
RMSNorm, rotary positions on half-split head vectors, causal grouped-query
attention, SwiGLU MLP, an output head that is the embedding's transpose
where the configuration ties them), written in ``jax.numpy`` at
``highest`` matmul precision. It imports nothing of the program: it reads
the weights that ``bench.weights`` made from the seed, one layer at a
time, and takes the token sequence a request was served.

``fp8=True`` is the control: every weight GEMM computed from operands
rounded to float8 e4m3 (per-tensor scale), the step below the bfloat16 the
configurations state.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import Shapes

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(a: jnp.ndarray) -> jnp.ndarray:
    """``a`` rounded to float8 e4m3 under a per-tensor scale, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        a, w = _q8(a), _q8(w)
    return a @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Shapes, fp8: bool, x, w):
    T = x.shape[0]
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    a = w["attn"]
    h = _rms(x, w["norm1"]["scale"], s.eps)
    q, k, v = (_mm(h, a[n], fp8) for n in ("wq", "wk", "wv"))
    if s.qkv_bias:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    pos = jnp.arange(T)
    q = _rope(q.reshape(T, H, hd), pos, s.rope_theta)
    k = _rope(k.reshape(T, KV, hd), pos, s.rope_theta)
    v = v.reshape(T, KV, hd)
    q = q.reshape(T, KV, H // KV, hd)
    sc = jnp.einsum("tkgd,ukd->kgtu", q, k) / np.sqrt(hd)
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", p, v).reshape(T, H * hd)
    x = x + _mm(o, a["wo"], fp8)
    m = w["mlp"]
    h = _rms(x, w["norm2"]["scale"], s.eps)
    y = jax.nn.silu(_mm(h, m["w_gate"], fp8)) * _mm(h, m["w_up"], fp8)
    return x + _mm(y, m["w_down"], fp8)


def _head(s: Shapes, fp8: bool, x, rows, norm, head):
    h = _rms(x[rows], norm, s.eps)
    return _mm(h, head, fp8)


@functools.lru_cache(maxsize=None)
def _fns(s: Shapes, fp8: bool):
    return (jax.jit(functools.partial(_layer, s, fp8)),
            jax.jit(functools.partial(_head, s, fp8)))


def logits_at(s: Shapes, params: Dict[str, Any], tokens: Sequence[int],
              rows: Sequence[int], *, pad_to: int, fp8: bool = False
              ) -> np.ndarray:
    """Logits (len(rows), V) of the causal forward over ``tokens``.

    ``tokens`` are padded at the end to ``pad_to`` (causal: the padding
    changes no earlier position), so sequences of one bucket share one
    compiled program.
    """
    layer, head = _fns(s, fp8)
    toks = np.zeros((pad_to,), np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for i in range(s.layers):
            w = jax.tree.map(lambda t: t[i], params["blocks"])
            x = layer(x, w)
        w = params["embed"].T if s.tied else params["lm_head"]
        out = head(x, jnp.asarray(np.asarray(rows, np.int32)),
                   params["final_norm"]["scale"], w)
    return np.asarray(out, np.float32)


def served_gaps(s: Shapes, params, prompt: Sequence[int],
                served: Sequence[int], *, pad_to: int) -> List[float]:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the reference's
    argmax)."""
    toks = list(prompt) + list(served)
    rows = [len(prompt) - 1 + j for j in range(len(served))]
    ref = logits_at(s, params, toks, rows, pad_to=pad_to)
    idx = np.asarray(served)
    return list(ref.max(-1) - ref[np.arange(len(idx)), idx])


def control_gaps(s: Shapes, params, prompt: Sequence[int],
                 served: Sequence[int], *, pad_to: int) -> List[float]:
    """The control at the same positions: the gap, in the float32
    reference, of the token that the fp8 reference puts first."""
    toks = list(prompt) + list(served)
    rows = [len(prompt) - 1 + j for j in range(len(served))]
    ref = logits_at(s, params, toks, rows, pad_to=pad_to)
    low = logits_at(s, params, toks, rows, pad_to=pad_to, fp8=True)
    pick = low.argmax(-1)
    return list(ref.max(-1) - ref[np.arange(len(pick)), pick])

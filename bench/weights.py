"""Weights and keys made from ``--seed``, by the benchmark and not the program.

The weights are made on the device in one jitted call, in bfloat16 (the
type they are served in), and already lie on the 4-of-8 tile pattern that
the serving cells prune to: within every (``group`` contraction rows ×
``block`` output columns) tile of a block GEMM, the same ``keep`` rows are
nonzero for all the tile's columns. The program's one-shot projection then
keeps every weight, so the plain reference can run on these same weights.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.spec import Shapes


def key_of(seed: int, *salt: int) -> jax.Array:
    """A PRNG key from a seed of any size up to 64 bits."""
    seed = int(seed)
    k = jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                           (seed >> 31) % (1 << 32))
    for s in salt:
        k = jax.random.fold_in(k, s)
    return k


def pattern_mask(key, lead: tuple, K: int, O: int, group: int, keep: int,
                 block: int) -> jnp.ndarray:
    """(lead..., K, O) bool: ``keep`` of every ``group`` rows per column block."""
    u = jax.random.uniform(key, lead + (K // group, group, O // block))
    rank = jnp.argsort(jnp.argsort(u, axis=-2), axis=-2)
    lanes = rank < keep                                   # (..., ng, g, nb)
    m = jnp.broadcast_to(lanes[..., None],
                         lanes.shape + (block,))          # (..., ng, g, nb, b)
    return m.reshape(lead + (K, O))


def _gemm(key, lead: tuple, K: int, O: int, s: Shapes, sparse: bool):
    kw, km = jax.random.split(key)
    w = jax.random.normal(kw, lead + (K, O), jnp.float32)
    if not sparse:
        return (w / jnp.sqrt(K)).astype(jnp.bfloat16)
    mask = pattern_mask(km, lead, K, O, s.group, s.keep, s.block)
    w = w * jnp.sqrt(s.group / s.keep) / jnp.sqrt(K)
    return jnp.where(mask, w, 0.0).astype(jnp.bfloat16)


def _scale(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2).astype(
        jnp.bfloat16)


def _make(s: Shapes, key) -> Dict[str, Any]:
    L, D = s.layers, s.d_model
    ks = iter(jax.random.split(key, 16))
    g = s.gemms()
    blocks: Dict[str, Any] = {
        "norm1": {"scale": _scale(next(ks), (L, D))},
        "norm2": {"scale": _scale(next(ks), (L, D))},
        "attn": {n: _gemm(next(ks), (L,), *g[n], s, n in s.packed)
                 for n in ("wq", "wk", "wv", "wo")},
        "mlp": {n: _gemm(next(ks), (L,), *g[n], s, n in s.packed)
                for n in ("w_gate", "w_up", "w_down")},
    }
    if s.qkv_bias:
        for b, n in (("bq", "wq"), ("bk", "wk"), ("bv", "wv")):
            blocks["attn"][b] = (0.1 * jax.random.normal(
                next(ks), (L, g[n][1]), jnp.float32)).astype(jnp.bfloat16)
    params = {
        "embed": (jax.random.normal(next(ks), (s.vocab, D), jnp.float32)
                  / jnp.sqrt(D)).astype(jnp.bfloat16),
        "blocks": blocks,
        "final_norm": {"scale": _scale(next(ks), (D,))},
    }
    if not s.tied:
        params["lm_head"] = _gemm(next(ks), (), *g["lm_head"], s,
                                  "lm_head" in s.packed)
    return params


def make_params(s: Shapes, seed: int) -> Dict[str, Any]:
    """The served weights of ``seed``: one jitted call, on the device."""
    return jax.jit(functools.partial(_make, s))(key_of(seed, 1))

"""Weights and keys made from ``--seed``, by the benchmark and not the program.

An architecture module (``bench/archs/<arch>.py``) makes its weights on the
device in one jitted call, in bfloat16 (the type they are served in), from
these pieces. A packed GEMM's weight already lies on the 4-of-8 tile
pattern that the serving cells prune to: within every (``group``
contraction rows × ``block`` output columns) tile, the same ``keep`` rows
are nonzero for all the tile's columns. The program's one-shot projection
then keeps every weight, so the plain reference can run on these same
weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def pattern_gemm_weight(key, lead: tuple, K: int, O: int, *, group: int,
                        keep: int, block: int, sparse: bool) -> jnp.ndarray:
    """(lead..., K, O) bfloat16 at std 1/sqrt(K); where ``sparse``, on the
    tile pattern, its kept weights scaled up to keep that std."""
    kw, km = jax.random.split(key)
    w = jax.random.normal(kw, lead + (K, O), jnp.float32)
    if not sparse:
        return (w / jnp.sqrt(K)).astype(jnp.bfloat16)
    mask = pattern_mask(km, lead, K, O, group, keep, block)
    w = w * jnp.sqrt(group / keep) / jnp.sqrt(K)
    return jnp.where(mask, w, 0.0).astype(jnp.bfloat16)


def norm_scale(key, shape) -> jnp.ndarray:
    """A norm's scale, uniform in [0.8, 1.2], in bfloat16."""
    return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2).astype(
        jnp.bfloat16)

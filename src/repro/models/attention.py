"""Attention: blockwise (flash-style) training/prefill path + cached decode.

Design notes (these choices show up directly in the roofline):

* **Q-chunk scan** (the §Perf-final formulation): outer scan over q-chunks
  whose per-chunk results stack via scan ``ys``; inner scan over the
  causal/window kv band. Online-softmax state is LOCAL to one q-chunk —
  no cross-step dynamic updates, which is what keeps GSPMD from gathering
  a full-sequence carry every step (EXPERIMENTS.md §Perf iter 1: the
  original pairs-scan formulation cost 937× collective bytes on phi4
  prefill; it is kept below as ``blockwise_attention_pairs`` for A/B).
* **Flash custom-VJP** (§Perf iter 5): backward recomputes score tiles
  chunk-wise from saved per-chunk (m, l) stats — two passes (dq; dk/dv) —
  instead of scan-AD stacking per-step tile residuals (2.6× train memory).
* **Online softmax**: carries (m, l, acc) in fp32; memory is O(S·d) + one
  (cq×ck) tile — never the full score matrix. The same VMEM-friendly
  formulation as `kernels/flash_attention.py`, which is the Pallas TPU
  serving path.
* **GQA**: queries grouped as (KV, G) so K/V are never materialized per
  Q-head.
* **Decode**: one query position against a cached K/V. Sliding-window archs
  use a RING buffer cache of size `window` with explicit per-slot positions,
  which is what makes `long_500k` memory-feasible (cache is O(window), not
  O(S)).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
_SUBLANES = 8            # rows of one TPU vector-register tile


def flash_prefill_supported(seq_len: int, num_heads: int, num_kv_heads: int,
                            *, block_q: int = 512, block_k: int = 512) -> bool:
    """Can ``kernels.flash_attention`` serve this prefill shape?

    The Pallas kernel tiles S by min(block, S) and groups q heads onto kv
    heads, so it needs S divisible by both (auto-true for S ≤ block) and an
    exact GQA ratio. Each block must also span whole 8-row sublane tiles:
    the TPU compiler refuses a bf16 kernel whose kv slices start off that
    tiling (a ragged S=37 prompt). Callers that get ``False`` keep the XLA
    blockwise path — the serve-path contract (``LM.prefill``).
    """
    if seq_len <= 0 or num_kv_heads <= 0:
        return False
    bq = min(block_q, seq_len)
    bk = min(block_k, seq_len)
    return (seq_len % bq == 0 and seq_len % bk == 0
            and bq % _SUBLANES == 0 and bk % _SUBLANES == 0
            and num_heads % num_kv_heads == 0)


def _chunk_pairs(
    num_q: int, num_kv: int, chunk: int, causal: bool, window: Optional[int]
) -> List[Tuple[int, int]]:
    """Static list of (qi, kj) chunk pairs with any unmasked entry."""
    pairs = []
    for qi in range(num_q):
        q_lo, q_hi = qi * chunk, (qi + 1) * chunk - 1
        for kj in range(num_kv):
            k_lo, k_hi = kj * chunk, (kj + 1) * chunk - 1
            if causal and k_lo > q_hi:
                continue  # entirely in the future
            if window is not None and k_hi < q_lo - (window - 1):
                continue  # entirely beyond the window
            pairs.append((qi, kj))
    return pairs


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "softmax_scale"),
)
def blockwise_attention(
    q: jnp.ndarray,                  # (B, S, H, hd)
    k: jnp.ndarray,                  # (B, S, KV, hd)
    v: jnp.ndarray,                  # (B, S, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,    # sliding-window width (tokens), None=full
    chunk: int = 512,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Flash-style attention: outer scan over q-chunks, inner over kv-chunks.

    §Perf iteration 1 (EXPERIMENTS.md): the previous pairs-scan carried a
    FULL-SEQUENCE (n, B, c, KV, G, hd) accumulator updated with
    dynamic-update-index every step — under pjit, GSPMD all-gathered that
    accumulator on EVERY pair step (54 TB/device for phi4 prefill_32k).
    This formulation keeps the online-softmax state PER Q-CHUNK inside a
    pure function whose results stack via scan ``ys`` — no cross-step
    dynamic updates, no gathered carry. Chunk-level mask skipping is traded
    for it (≤2× attention-FLOP waste, invisible next to the memory term;
    sliding-window keeps its O(S·W) via a static band of kv-chunks).
    """
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(
        q.shape[-1])
    fn = _flash_vjp(causal, window, min(chunk, q.shape[1]), float(scale))
    return fn(q, k, v)


def _blockwise_qchunk(q, k, v, *, causal, window, chunk, softmax_scale):
    """Plain (AD-differentiable) q-chunk formulation — used by tests/A-B."""
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(
        q.shape[-1])
    out, _, _ = _qchunk_fwd(q, k, v, causal=causal, window=window,
                            chunk=min(chunk, q.shape[1]), scale=float(scale))
    return out


def _chunk_mask(q_pos, k_pos, causal, window):
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    return ok


def _qchunk_fwd(q, k, v, *, causal, window, chunk, scale):
    """Outer scan over q-chunks; returns (out, m, l) — stats for the VJP."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if S % chunk != 0:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    n = S // chunk
    # static band of kv-chunks per q-chunk: the window band for causal SWA
    # (O(S·W) — what makes long_500k feasible); all n chunks otherwise.
    # A non-causal window bounds only the PAST (q_pos - k_pos < window), so
    # the band shortcut applies to causal windows only.
    band = (min(n, (window - 1) // chunk + 2)
            if (window is not None and causal) else n)

    # §Perf: pre-scale q so the (c×c) score tile needs no scale multiply
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qs.reshape(B, n, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    pos = jnp.arange(chunk, dtype=jnp.int32)

    def q_chunk_step(_, xs):
        qc, qi = xs                                  # (B, c, KV, G, hd)
        q_pos = qi * chunk + pos                     # (c,)
        j0 = jnp.maximum(qi - (band - 1), 0) if band < n else jnp.int32(0)

        def inner(carry, jj):
            m, l, acc = carry
            kj = j0 + jj
            kc = jax.lax.dynamic_slice_in_dim(k, kj * chunk, chunk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, kj * chunk, chunk, axis=1)
            s = jnp.einsum("bqkgd,bpkd->bqpkg", qc, kc,
                           preferred_element_type=jnp.float32)
            ok = _chunk_mask(q_pos, kj * chunk + pos, causal, window)
            s = jnp.where(ok[None, :, :, None, None], s, NEG_INF)

            s_max = jnp.max(s, axis=2)                # (B, c, KV, G)
            m_new = jnp.maximum(m, s_max)
            p = jnp.exp(s - m_new[:, :, None, :, :])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=2)
            pv = jnp.einsum("bqpkg,bpkd->bqkgd", p.astype(v.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, chunk, KV, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, chunk, KV, G), jnp.float32)
        a0 = jnp.zeros((B, chunk, KV, G, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(inner, (m0, l0, a0),
                                      jnp.arange(band, dtype=jnp.int32))
        out_c = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, (out_c.astype(q.dtype), m, l)    # (B, c, KV, G, hd)

    _, (out, m_all, l_all) = jax.lax.scan(
        q_chunk_step, None, (qg, jnp.arange(n, dtype=jnp.int32))
    )                                                  # (n, B, c, KV, G, …)
    out_f = jnp.moveaxis(out, 0, 1).reshape(B, S, KV, G, hd)
    return out_f.reshape(B, S, H, hd).astype(q.dtype), m_all, l_all


def _qchunk_bwd_impl(q, k, v, out, m_all, l_all, dout, *, causal, window,
                     chunk, scale):
    """Flash-style backward (§Perf iteration 5): recompute score tiles
    chunk-wise instead of letting scan-AD stack per-step tile residuals.

    Two passes (standard flash backward):
      A) dq — outer scan over q-chunks, inner over the kv band;
      B) dk/dv — outer scan over kv-chunks, inner over the q band.
    Per-chunk stats (m, l) from the forward make p reproducible exactly:
    p = exp(s − m)/l. No stacked (band, c, c) residuals, no
    dynamic-update-gather carries — the pathologies this replaces.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    n = S // chunk
    band = (min(n, (window - 1) // chunk + 2)
            if (window is not None and causal) else n)
    pos = jnp.arange(chunk, dtype=jnp.int32)
    f32 = jnp.float32

    qsc = (q.astype(f32) * scale).astype(q.dtype)
    qg = qsc.reshape(B, n, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    do = dout.reshape(B, n, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    og = out.reshape(B, n, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    # D = rowsum(dout ⊙ out): (n, B, c, KV, G)
    D = jnp.sum(do.astype(f32) * og.astype(f32), axis=-1)
    linv = 1.0 / jnp.maximum(l_all, 1e-30)

    def p_tile(qc, kc, mc, lic, q_pos, k_pos):
        s = jnp.einsum("bqkgd,bpkd->bqpkg", qc, kc,
                       preferred_element_type=f32)
        ok = _chunk_mask(q_pos, k_pos, causal, window)
        s = jnp.where(ok[None, :, :, None, None], s, NEG_INF)
        return jnp.exp(s - mc[:, :, None, :, :]) * lic[:, :, None, :, :]

    # ---- pass A: dq ---------------------------------------------------
    def dq_step(_, xs):
        qc, doc, Dc, mc, lic, qi = xs
        q_pos = qi * chunk + pos
        j0 = jnp.maximum(qi - (band - 1), 0) if band < n else jnp.int32(0)

        def inner(dqc, jj):
            kj = j0 + jj
            kc = jax.lax.dynamic_slice_in_dim(k, kj * chunk, chunk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, kj * chunk, chunk, axis=1)
            p = p_tile(qc, kc, mc, lic, q_pos, kj * chunk + pos)
            dP = jnp.einsum("bqkgd,bpkd->bqpkg", doc, vc,
                            preferred_element_type=f32)
            ds = p * (dP - Dc[:, :, None, :, :])
            dqc = dqc + jnp.einsum("bqpkg,bpkd->bqkgd",
                                   ds.astype(k.dtype), kc,
                                   preferred_element_type=f32)
            return dqc, None

        dq0 = jnp.zeros((B, chunk, KV, G, hd), f32)
        dqc, _ = jax.lax.scan(inner, dq0, jnp.arange(band, dtype=jnp.int32))
        return None, (dqc * scale).astype(q.dtype)

    _, dq = jax.lax.scan(
        dq_step, None,
        (qg, do, D, m_all, linv, jnp.arange(n, dtype=jnp.int32)),
    )
    dq = jnp.moveaxis(dq, 0, 1).reshape(B, S, H, hd)

    # ---- pass B: dk, dv -----------------------------------------------
    # q band attending to kv-chunk kj: [kj, kj+band) under CAUSAL
    # (window-banded when SWA); all n chunks otherwise
    qband = band if causal else n

    def dkv_step(_, xs):
        kc, vc, kj = xs
        k_pos = kj * chunk + pos
        j0 = kj if causal else jnp.int32(0)

        def inner(carry, jj):
            dkc, dvc = carry
            qi = jnp.minimum(j0 + jj, n - 1)
            valid = (j0 + jj) <= (n - 1)
            qc = jax.lax.dynamic_index_in_dim(qg, qi, axis=0, keepdims=False)
            doc = jax.lax.dynamic_index_in_dim(do, qi, axis=0, keepdims=False)
            Dc = jax.lax.dynamic_index_in_dim(D, qi, axis=0, keepdims=False)
            mc = jax.lax.dynamic_index_in_dim(m_all, qi, axis=0,
                                              keepdims=False)
            lic = jax.lax.dynamic_index_in_dim(linv, qi, axis=0,
                                               keepdims=False)
            p = p_tile(qc, kc, mc, lic, qi * chunk + pos, k_pos)
            p = p * valid.astype(f32)
            dvc = dvc + jnp.einsum("bqpkg,bqkgd->bpkd",
                                   p.astype(do.dtype), doc,
                                   preferred_element_type=f32)
            dP = jnp.einsum("bqkgd,bpkd->bqpkg", doc, vc,
                            preferred_element_type=f32)
            ds = p * (dP - Dc[:, :, None, :, :])
            dkc = dkc + jnp.einsum("bqpkg,bqkgd->bpkd",
                                   ds.astype(q.dtype), qc,
                                   preferred_element_type=f32)
            return (dkc, dvc), None

        z = jnp.zeros((B, chunk, KV, hd), f32)
        (dkc, dvc), _ = jax.lax.scan(inner, (z, z),
                                     jnp.arange(qband, dtype=jnp.int32))
        return None, (dkc.astype(k.dtype), dvc.astype(v.dtype))

    ks = k.reshape(B, n, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, n, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    _, (dk, dv) = jax.lax.scan(
        dkv_step, None, (ks, vs, jnp.arange(n, dtype=jnp.int32))
    )
    dk = jnp.moveaxis(dk, 0, 1).reshape(B, S, KV, hd)
    dv = jnp.moveaxis(dv, 0, 1).reshape(B, S, KV, hd)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _flash_vjp(causal: bool, window: Optional[int], chunk: int, scale: float):
    """custom_vjp'd q-chunk attention for one static configuration."""

    @jax.custom_vjp
    def f(q, k, v):
        out, _, _ = _qchunk_fwd(q, k, v, causal=causal, window=window,
                                chunk=chunk, scale=scale)
        return out

    def fwd(q, k, v):
        out, m, l = _qchunk_fwd(q, k, v, causal=causal, window=window,
                                chunk=chunk, scale=scale)
        return out, (q, k, v, out, m, l)

    def bwd(res, dout):
        q, k, v, out, m, l = res
        return _qchunk_bwd_impl(q, k, v, out, m, l, dout, causal=causal,
                                window=window, chunk=chunk, scale=scale)

    f.defvjp(fwd, bwd)
    return f


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "softmax_scale"),
)
def blockwise_attention_pairs(
    q: jnp.ndarray,                  # (B, S, H, hd)
    k: jnp.ndarray,                  # (B, S, KV, hd)
    v: jnp.ndarray,                  # (B, S, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,    # sliding-window width (tokens), None=full
    chunk: int = 512,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Pairs-scan formulation (§Perf baseline — kept for A/B comparison)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(hd)
    chunk = min(chunk, S)
    if S % chunk != 0:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    n = S // chunk

    pairs = _chunk_pairs(n, n, chunk, causal, window)
    pairs_arr = jnp.asarray(pairs, dtype=jnp.int32)          # (P, 2)

    qg = q.reshape(B, S, KV, G, hd)

    # fp32 online-softmax accumulators
    m0 = jnp.full((n, B, chunk, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, B, chunk, KV, G), jnp.float32)
    acc0 = jnp.zeros((n, B, chunk, KV, G, hd), jnp.float32)

    pos = jnp.arange(chunk, dtype=jnp.int32)

    def body(carry, pair):
        m, l, acc = carry
        qi, kj = pair[0], pair[1]
        qc = jax.lax.dynamic_slice_in_dim(qg, qi * chunk, chunk, axis=1)
        kc = jax.lax.dynamic_slice_in_dim(k, kj * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, kj * chunk, chunk, axis=1)

        s = jnp.einsum(
            "bqkgd,bpkd->bqpkg", qc, kc,
            preferred_element_type=jnp.float32,
        ) * scale                                             # (B,cq,ck,KV,G)

        q_pos = qi * chunk + pos                              # (cq,)
        k_pos = kj * chunk + pos                              # (ck,)
        ok = jnp.ones((chunk, chunk), bool)
        if causal:
            ok &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            ok &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(ok[None, :, :, None, None], s, NEG_INF)

        mq = jax.lax.dynamic_index_in_dim(m, qi, axis=0, keepdims=False)
        lq = jax.lax.dynamic_index_in_dim(l, qi, axis=0, keepdims=False)
        aq = jax.lax.dynamic_index_in_dim(acc, qi, axis=0, keepdims=False)

        s_max = jnp.max(s, axis=2)                            # (B,cq,KV,G)
        m_new = jnp.maximum(mq, s_max)
        p = jnp.exp(s - m_new[:, :, None, :, :])
        corr = jnp.exp(mq - m_new)
        l_new = lq * corr + jnp.sum(p, axis=2)
        pv = jnp.einsum("bqpkg,bpkd->bqkgd", p.astype(v.dtype), vc,
                        preferred_element_type=jnp.float32)
        a_new = aq * corr[..., None] + pv

        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, axis=0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, axis=0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, axis=0)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), pairs_arr)

    out = acc / jnp.maximum(l, 1e-30)[..., None]              # (n,B,c,KV,G,hd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, KV, G, hd)
    return out.reshape(B, S, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CacheSpec:
    """Static description of a per-layer KV cache."""

    capacity: int            # S_max for full caches; window for ring caches
    ring: bool               # True → sliding-window ring buffer


def cache_capacity(seq_len: int, window: Optional[int]) -> CacheSpec:
    if window is not None and window < seq_len:
        return CacheSpec(capacity=window, ring=True)
    return CacheSpec(capacity=seq_len, ring=False)


def slot_prompt_rows(capacity: int, prompt_len: int, ring: bool):
    """Cache geometry for writing a fresh ``prompt_len``-token prompt.

    Returns ``(rows, keep, slot_pos_row)``: the cache slot indices
    ``(keep,)`` the prompt's LAST ``keep`` positions land in (ring caches
    keep only the trailing window), and the full ``(capacity,)`` slot_pos
    row for the slot — fresh positions where written, ``-1`` (empty →
    masked by ``decode_attention``) everywhere else. Resetting a slot's
    row to this is what invalidates a retired occupant's stale KV when a
    batch slot is reused mid-decode: the bytes stay, the mask hides them.

    The geometry is static, so it is built in NumPy and enters the program
    as constants: traced, the row is a scatter whose indices and updates
    come from one iota, and the TPU compiler (libtpu 0.0.34) aborts on
    that scatter.
    """
    S, C = prompt_len, capacity
    if not ring and S > C:
        raise ValueError(f"prompt_len={S} exceeds cache capacity={C}")
    keep = min(C, S)
    pos = np.arange(S - keep, S, dtype=np.int32)
    rows = pos % C if ring else pos
    slot_pos_row = np.full((C,), -1, np.int32)
    slot_pos_row[rows] = pos
    return rows, keep, slot_pos_row


def decode_attention(
    q: jnp.ndarray,                  # (B, 1, H, hd) — one new position
    k_cache: jnp.ndarray,            # (B, C, KV, hd)
    v_cache: jnp.ndarray,            # (B, C, KV, hd)
    slot_pos: jnp.ndarray,           # (B, C) int32 position per slot, -1=empty
    q_pos: jnp.ndarray,              # (B,) int32 current position
    *,
    window: Optional[int] = None,
    chunk: int = 2048,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """One decode step against the cache (chunked over cache slots)."""
    B, C, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(hd)
    chunk = min(chunk, C)
    pad = (-C) % chunk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, pad)), constant_values=-1)
    nC = k_cache.shape[1] // chunk

    qg = q.reshape(B, KV, G, hd)

    if nC == 1:
        # single-chunk fast path: the whole cache fits one tile — plain
        # masked softmax, no running-max loop machinery (decode caches are
        # usually small; this trims a per-layer per-step while loop)
        s = jnp.einsum("bkgd,bpkd->bkgp", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        ok = (slot_pos >= 0) & (slot_pos <= q_pos[:, None])
        if window is not None:
            ok &= q_pos[:, None] - slot_pos < window
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgp,bpkd->bkgd", p.astype(v_cache.dtype), v_cache,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, 1, H, hd).astype(q.dtype)

    def body(carry, j):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k_cache, j * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v_cache, j * chunk, chunk, axis=1)
        sp = jax.lax.dynamic_slice_in_dim(slot_pos, j * chunk, chunk, axis=1)

        s = jnp.einsum("bkgd,bpkd->bkgp", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        ok = (sp >= 0) & (sp[:, :] <= q_pos[:, None])
        if window is not None:
            ok &= q_pos[:, None] - sp < window
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)

        s_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, s_max)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgp,bpkd->bkgd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)
    a0 = jnp.zeros((B, KV, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nC))

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def chunk_rows(pos: jnp.ndarray, K: int, capacity: int, ring: bool
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cache slot indices for the next ``K`` positions of every batch row.

    Returns ``(idx, rows)``: ``idx (B, K)`` are the absolute positions
    ``pos[b] .. pos[b]+K-1`` and ``rows (B, K)`` the cache slots they land
    in (``idx % C`` for ring buffers, ``idx`` otherwise — non-ring rows
    past capacity are left unclamped so scatters DROP them, which is the
    documented overflow behavior for slots that decode past their budget).
    """
    idx = pos[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    rows = idx % capacity if ring else idx
    return idx, rows


def chunk_attention(
    q: jnp.ndarray,                  # (B, K, H, hd) — K new positions
    k_cache: jnp.ndarray,            # (B, C, KV, hd), chunk KV already inserted
    v_cache: jnp.ndarray,
    slot_pos: jnp.ndarray,           # (B, C) int32 position per slot, -1=empty
    q_pos: jnp.ndarray,              # (B, K) int32 per-query positions
    *,
    window: Optional[int] = None,
    chunk: int = 2048,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """K decode positions against the cache in ONE attention call.

    The chunked-verify generalization of ``decode_attention``: the caller
    inserts all K positions' k/v into the cache FIRST (``cache_insert_chunk``)
    and per-query causal masking over ``slot_pos`` then covers intra-chunk
    causality for free — chunk query i sees chunk key j iff
    ``slot_pos = pos+j <= pos+i``. Same fp32 online-softmax formulation
    (and the same single-tile fast path) as ``decode_attention``, with an
    extra query axis.
    """
    B, C, KV, hd = k_cache.shape
    K, H = q.shape[1], q.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(hd)
    chunk = min(chunk, C)
    pad = (-C) % chunk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, pad)), constant_values=-1)
    nC = k_cache.shape[1] // chunk

    qg = q.reshape(B, K, KV, G, hd)

    def tile_mask(sp):                                # sp (B, c) → (B, K, c)
        ok = (sp[:, None, :] >= 0) & (sp[:, None, :] <= q_pos[:, :, None])
        if window is not None:
            ok &= q_pos[:, :, None] - sp[:, None, :] < window
        return ok

    if nC == 1:
        s = jnp.einsum("bqkgd,bpkd->bqkgp", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(tile_mask(slot_pos)[:, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bqkgp,bpkd->bqkgd", p.astype(v_cache.dtype),
                         v_cache, preferred_element_type=jnp.float32)
        return out.reshape(B, K, H, hd).astype(q.dtype)

    def body(carry, j):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k_cache, j * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v_cache, j * chunk, chunk, axis=1)
        sp = jax.lax.dynamic_slice_in_dim(slot_pos, j * chunk, chunk, axis=1)

        s = jnp.einsum("bqkgd,bpkd->bqkgp", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(tile_mask(sp)[:, :, None, None, :], s, NEG_INF)

        s_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, s_max)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqkgp,bpkd->bqkgd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, KV, G), jnp.float32)
    a0 = jnp.zeros((B, K, KV, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nC))

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, K, H, hd).astype(q.dtype)


def cache_insert_chunk(
    k_cache: jnp.ndarray,            # (B, C, KV, hd)
    v_cache: jnp.ndarray,
    slot_pos: jnp.ndarray,           # (B, C)
    k_new: jnp.ndarray,              # (B, K, KV, hd)
    v_new: jnp.ndarray,
    pos: jnp.ndarray,                # (B,) int32 — first position of the chunk
    *,
    ring: bool,
):
    """Insert K consecutive positions per batch row (chunked verify path).

    Ring caches require ``K <= C`` so the chunk's rows are distinct per
    batch row (a verify chunk longer than the sliding window could not
    sit in the cache at once anyway — ``LM.verify_chunk`` validates).
    Non-ring rows past capacity scatter-drop, matching ``chunk_rows``.
    """
    C = k_cache.shape[1]
    idx, rows = chunk_rows(pos, k_new.shape[1], C, ring)
    b = jnp.arange(k_cache.shape[0])[:, None]
    k_cache = k_cache.at[b, rows].set(k_new.astype(k_cache.dtype))
    v_cache = v_cache.at[b, rows].set(v_new.astype(v_cache.dtype))
    slot_pos = slot_pos.at[b, rows].set(idx)
    return k_cache, v_cache, slot_pos


def cache_insert(
    k_cache: jnp.ndarray,            # (B, C, KV, hd), or (L, B, C, KV, hd)
    v_cache: jnp.ndarray,
    slot_pos: jnp.ndarray,           # (B, C)
    k_new: jnp.ndarray,              # (B, 1, KV, hd)
    v_new: jnp.ndarray,
    pos,                             # (B,) int32, or (layer, (B,) int32)
    *,
    ring: bool,
):
    """Insert one position into the cache (ring: slot = pos % C).

    Per-batch scatter into the target slot: touches B·KV·hd elements
    instead of blending over the whole (B, C, KV, hd) cache. ``pos`` given
    as ``(layer, pos)`` names one layer of a layer-stacked
    (L, B, C, KV, hd) cache, and the rows land at ``[layer, b, slot]``.
    That is the decode step's form: ``LM.decode_many``'s scan over steps
    and ``LM.decode_step``'s scan over layers both carry the stacked
    buffers, so each layer's insert is a one-row scatter that XLA does in
    place, and no layer's slab is copied out of the stack or back into it.
    """
    layer = ()                                                # index prefix
    if isinstance(pos, tuple):
        layer, pos = pos[:1], pos[1]
    b = jnp.arange(pos.shape[0])
    _, rows = chunk_rows(pos, 1, k_cache.shape[-3], ring)
    slot = rows[:, 0]                                         # (B,)
    k_cache = k_cache.at[layer + (b, slot)].set(
        k_new[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[layer + (b, slot)].set(
        v_new[:, 0].astype(v_cache.dtype))
    slot_pos = slot_pos.at[b, slot].set(pos)
    return k_cache, v_cache, slot_pos

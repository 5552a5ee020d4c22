"""Qwen2 dense decoder: everything of the benchmark that knows its shape.

An architecture module is found by the ``"arch"`` key of a configuration
file (``spec.load_arch``) and gives the rest of the benchmark:

    Shapes, Shapes.of(cfg)   the sizes it computes with; callers outside
                             rely only on layers, vocab, heads, kv_heads,
                             head_dim and packed
    model_config(cfg)        the program's ``ModelConfig``
    make_params(s, seed)     the served weights of a seed (``_make``, jitted)
    logits_at(...)           the float32 reference, with the float8 control
    prefill, decode_token_flops, decode_step, weight_bytes,
    prefill_kernels          the work a step requires, from shapes
    small_config(cfg)        the cut the CPU tests run

Qwen2 (hf ``Qwen2ForCausalLM``): RMSNorm, rotary positions on half-split
head vectors, causal grouped-query attention with bias on q, k and v, a
SwiGLU MLP, and an output head that is the embedding's transpose where
the configuration ties them. Another architecture that shares a part
(the attention of Qwen2-MoE) imports it from here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mm, rms, rope
from bench.weights import key_of, norm_scale, pattern_gemm_weight
from bench.work import BF16, LANE, Work, dense_gemm, flash_prefill, \
    packed_gemm


# ---------------------------------------------------------------------------
# shapes and the program's configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes the yardstick computes with, read from a config file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    eps: float
    group: int
    keep: int
    block: int
    packed: tuple
    tied: bool = False       # the output head is the embedding, transposed

    @classmethod
    def of(cls, cfg: Dict[str, Any]) -> "Shapes":
        p = cfg["prune"]
        return cls(
            layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["assumed"]["head_dim"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            qkv_bias=cfg["assumed"]["qkv_bias"],
            rope_theta=float(cfg["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]), group=p["group"],
            keep=p["keep"], block=p["tile_block"],
            packed=tuple(cfg["packed_leaves"]),
            tied=bool(cfg["tie_word_embeddings"]))

    def gemms(self) -> Dict[str, tuple]:
        """(K, O) of every GEMM of one block, and of the output head
        (``lm_head``, the embedding's transpose where ``tied``)."""
        D, A, KV, F = (self.d_model, self.heads * self.head_dim,
                       self.kv_heads * self.head_dim, self.d_ff)
        return {"wq": (D, A), "wk": (D, KV), "wv": (D, KV), "wo": (A, D),
                "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
                "lm_head": (D, self.vocab)}


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` at the sizes this file states."""
    from repro.configs import get_config

    base = get_config(cfg["repo_config"])
    heads = cfg["num_attention_heads"]
    return dataclasses.replace(
        base,
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["assumed"]["head_dim"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["assumed"]["qkv_bias"],
        rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"],
        sliding_window=None,
        family="dense",
        ffn_type="swiglu",
    )


def small_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``cfg`` with its keys at widths the CPU runs in seconds."""
    cfg = dict(cfg, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=512)
    cfg["assumed"] = dict(cfg["assumed"], head_dim=16)
    cfg["prune"] = dict(cfg["prune"], tile_block=32)
    return cfg


# ---------------------------------------------------------------------------
# weights (``bench.weights`` says how they are made)
# ---------------------------------------------------------------------------

def gemm_weight(key, lead: tuple, K: int, O: int, s: Shapes, name: str):
    """The weight of GEMM ``name``, on the tile pattern where it is
    packed."""
    return pattern_gemm_weight(key, lead, K, O, group=s.group, keep=s.keep,
                               block=s.block, sparse=name in s.packed)


def _make(s: Shapes, key) -> Dict[str, Any]:
    L, D = s.layers, s.d_model
    ks = iter(jax.random.split(key, 16))
    g = s.gemms()
    blocks: Dict[str, Any] = {
        "norm1": {"scale": norm_scale(next(ks), (L, D))},
        "norm2": {"scale": norm_scale(next(ks), (L, D))},
        "attn": {n: gemm_weight(next(ks), (L,), *g[n], s, n)
                 for n in ("wq", "wk", "wv", "wo")},
        "mlp": {n: gemm_weight(next(ks), (L,), *g[n], s, n)
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
        "final_norm": {"scale": norm_scale(next(ks), (D,))},
    }
    if not s.tied:
        params["lm_head"] = gemm_weight(next(ks), (), *g["lm_head"], s,
                                        "lm_head")
    return params


def make_params(s: Shapes, seed: int) -> Dict[str, Any]:
    """The served weights of ``seed``: one jitted call, on the device."""
    return jax.jit(functools.partial(_make, s))(key_of(seed, 1))


# ---------------------------------------------------------------------------
# the plain reference, in float32 at ``highest`` matmul precision
# ---------------------------------------------------------------------------

def attention(s: Shapes, fp8: bool, x, w):
    """``x`` plus the causal grouped-query attention of one block."""
    T = x.shape[0]
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    a = w["attn"]
    h = rms(x, w["norm1"]["scale"], s.eps)
    q, k, v = (mm(h, a[n], fp8) for n in ("wq", "wk", "wv"))
    if s.qkv_bias:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    pos = jnp.arange(T)
    q = rope(q.reshape(T, H, hd), pos, s.rope_theta)
    k = rope(k.reshape(T, KV, hd), pos, s.rope_theta)
    v = v.reshape(T, KV, hd)
    q = q.reshape(T, KV, H // KV, hd)
    sc = jnp.einsum("tkgd,ukd->kgtu", q, k) / np.sqrt(hd)
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", p, v).reshape(T, H * hd)
    return x + mm(o, a["wo"], fp8)


def _layer(s: Shapes, fp8: bool, x, w):
    x = attention(s, fp8, x, w)
    m = w["mlp"]
    h = rms(x, w["norm2"]["scale"], s.eps)
    y = jax.nn.silu(mm(h, m["w_gate"], fp8)) * mm(h, m["w_up"], fp8)
    return x + mm(y, m["w_down"], fp8)


def _head(s: Shapes, fp8: bool, x, rows, norm, head):
    h = rms(x[rows], norm, s.eps)
    return mm(h, head, fp8)


@functools.lru_cache(maxsize=None)
def _fns(s: Shapes, fp8: bool):
    return (jax.jit(functools.partial(_layer, s, fp8)),
            jax.jit(functools.partial(_head, s, fp8)))


def logits_at(s: Shapes, params: Dict[str, Any], tokens: Sequence[int],
              rows: Sequence[int], *, pad_to: int, fp8: bool = False
              ) -> np.ndarray:
    """Logits (len(rows), V) of the causal forward over ``tokens``, one
    layer at a time; ``fp8`` computes every weight GEMM from operands
    rounded to float8 (the control).

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


# ---------------------------------------------------------------------------
# the work a step requires
# ---------------------------------------------------------------------------

BLOCK_GEMMS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def gemm(s: Shapes, name: str, M: int) -> Work:
    K, O = s.gemms()[name]
    bias = s.qkv_bias and name in ("wq", "wk", "wv")
    if name in s.packed:
        return packed_gemm(M, K, O, keep=s.keep, group=s.group,
                           block=s.block, bias=bias)
    return dense_gemm(M, K, O, bias=bias)


def prefill(s: Shapes, S: int) -> Work:
    """One solo prefill of ``S`` tokens: every block at M = S, then the
    output head on the last position."""
    block = sum((gemm(s, n, S) for n in BLOCK_GEMMS), Work())
    block = block + flash_prefill(S, s.heads, s.kv_heads, s.head_dim)
    return block * s.layers + gemm(s, "lm_head", 1)


def prefill_kernels(s: Shapes, S: int) -> Dict[str, Tuple[int, Work]]:
    """Per Pallas kernel that a prefill of ``S`` tokens runs: its calls in
    one block, and the work they require together."""
    names = [n for n in BLOCK_GEMMS if n in s.packed]
    return {
        "pattern_gemm": (len(names),
                         sum((gemm(s, n, S) for n in names), Work())),
        "flash_attention": (1, flash_prefill(S, s.heads, s.kv_heads,
                                             s.head_dim)),
    }


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

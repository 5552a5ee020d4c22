"""Unified LM: one scan-over-layers model covering all 10 assigned archs.

Families:
  dense / vlm       — GQA attention (+optional SWA, QKV bias) + SwiGLU FFN
  moe               — GQA attention + shared/routed top-k MoE FFN
  audio             — bidirectional encoder (HuBERT backbone), GELU FFN
  ssm               — xLSTM: groups of (slstm_every-1) mLSTM + 1 sLSTM blocks
  hybrid            — hymba: parallel attention + mamba heads, SwiGLU FFN

Structure decisions that matter at scale:
  * Layers are SCAN-STACKED: every block weight carries a leading layer dim
    and the forward is a single lax.scan — HLO size is O(1) in depth, which
    is what keeps 48-layer × 512-device compiles tractable (same approach as
    MaxText).
  * The loss never materializes (B, S, V) logits: cross-entropy is computed
    in sequence chunks under jax.checkpoint (vocab up to 200k × 32k seq
    would otherwise dominate activation memory).
  * Decode uses explicit caches (KV ring-buffers for SWA, recurrent states
    for ssm/hybrid) — `long_500k` works because no full-attention arch ever
    reaches it (assignment skip rule) and SWA/SSM caches are O(window)/O(1).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import (
    blockwise_attention,
    cache_capacity,
    cache_insert,
    chunk_attention,
    chunk_rows,
    decode_attention,
    flash_prefill_supported,
    slot_prompt_rows,
)
from repro.models.layers import (
    dense_apply,
    dense_init,
    dtype_of,
    embed_init,
    ffn_apply,
    ffn_init,
    rmsnorm,
    rmsnorm_init,
    apply_rope,
    apply_rope_tables,
    rope_tables,
)
from repro.models.moe import moe_apply, moe_init
from repro.parallel.sharding import constrain
from repro.runtime.telemetry import get_registry

MOE_AUX_COEF = 0.01
LOSS_CHUNK = 512


def _kernels_compiled() -> bool:
    """True where Pallas kernels compile for the device (not interpret)."""
    from repro.kernels.ops import _default_interpret

    return not _default_interpret()


@dataclasses.dataclass
class LM:
    config: ModelConfig

    # ------------------------------------------------------------------ init

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        dt = dtype_of(cfg.param_dtype)
        k_embed, k_blocks, k_head = jax.random.split(key, 3)

        params: Dict[str, Any] = {}
        if cfg.input_kind == "tokens":
            params["embed"] = embed_init(k_embed, cfg.vocab_size, cfg.d_model, dt)

        if cfg.family == "ssm":
            params["blocks"] = self._init_xlstm_blocks(k_blocks, dt)
        else:
            keys = jax.random.split(k_blocks, cfg.num_layers)
            params["blocks"] = jax.vmap(lambda k: self._init_block(k, dt))(keys)

        params["final_norm"] = rmsnorm_init(cfg.d_model, dt)
        if not (cfg.tie_embeddings and cfg.input_kind == "tokens"):
            params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dt)
        return params

    def _init_block(self, key: jax.Array, dt) -> Dict[str, Any]:
        cfg = self.config
        ks = jax.random.split(key, 6)
        block: Dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model, dt)}

        attn = {
            "wq": dense_init(ks[0], cfg.d_model, cfg.attn_dim, dt),
            "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, dt),
            "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, dt),
            "wo": dense_init(ks[3], cfg.attn_dim, cfg.d_model, dt),
        }
        if cfg.qkv_bias:
            attn["bq"] = jnp.zeros((cfg.attn_dim,), dt)
            attn["bk"] = jnp.zeros((cfg.kv_dim,), dt)
            attn["bv"] = jnp.zeros((cfg.kv_dim,), dt)
        block["attn"] = attn
        block["norm2"] = rmsnorm_init(cfg.d_model, dt)

        if cfg.num_experts:
            block["moe"] = moe_init(
                ks[4], cfg.d_model, cfg.num_experts, cfg.num_shared_experts,
                cfg.expert_d_ff, dt,
            )
        elif cfg.d_ff:
            block["mlp"] = ffn_init(ks[4], cfg.d_model, cfg.d_ff, cfg.ffn_type, dt)

        if cfg.family == "hybrid":
            d_inner = cfg.mamba_heads * cfg.mamba_head_dim
            block["mamba"] = ssm_mod.mamba_init(
                ks[5], cfg.d_model, d_inner, cfg.ssm_state, cfg.conv_kernel, dt
            )
        return block

    def _init_xlstm_blocks(self, key: jax.Array, dt) -> Dict[str, Any]:
        cfg = self.config
        G, per = self._xlstm_groups()
        n_m = per - 1
        km, ks_ = jax.random.split(key)

        def init_m(k):
            return ssm_mod.mlstm_init(
                k, cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.conv_kernel, dt
            ) | {"norm": rmsnorm_init(cfg.d_model, dt)}

        def init_s(k):
            return ssm_mod.slstm_init(k, cfg.d_model, cfg.num_heads, dt) | {
                "norm": rmsnorm_init(cfg.d_model, dt)
            }

        mkeys = jax.random.split(km, G * n_m).reshape(G, n_m, 2)
        skeys = jax.random.split(ks_, G)
        return {
            "mlstm": jax.vmap(jax.vmap(init_m))(mkeys),
            "slstm": jax.vmap(init_s)(skeys),
        }

    def _xlstm_groups(self) -> Tuple[int, int]:
        cfg = self.config
        per = cfg.slstm_every if cfg.slstm_every else cfg.num_layers
        if cfg.num_layers % per != 0:
            raise ValueError("num_layers must divide by slstm_every")
        return cfg.num_layers // per, per

    # --------------------------------------------------------------- shardings

    def param_logical_axes(self) -> Dict[str, Any]:
        """Pytree (congruent with params) of logical-axis tuples."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))

        def leaf_axes(path: str, x) -> tuple:
            nd = len(x.shape)
            if path == "embed":
                return ("vocab", "embed")
            if path == "lm_head":
                return ("embed", "vocab")
            lead: tuple = ("layers",) * (nd - self._leaf_rank(path, x))
            base = self._logical_for(path, nd - len(lead))
            return lead + base

        from repro.utils.tree import tree_map_with_path_str

        return tree_map_with_path_str(leaf_axes, shapes)

    @staticmethod
    def _leaf_rank(path: str, x) -> int:
        """Rank of the per-layer tensor (strip scan-stack leading dims)."""
        nd = len(x.shape)
        if path in ("embed", "lm_head") or path.startswith("final_norm"):
            return nd
        if "blocks/mlstm" in path:
            return nd - 2                     # (G, per-1, ...) stacking
        if "blocks/" in path:
            return nd - 1                     # (L, ...) or (G, ...) stacking
        return nd

    @staticmethod
    def _logical_for(path: str, rank: int) -> tuple:
        """Logical axes of the per-layer tensor by param name."""
        name = path.split("/")[-1]
        owner = path.split("/")[-2] if "/" in path else ""
        if rank == 0:
            return ()
        if rank == 1:
            return (None,)
        if owner == "experts":                # (E, D, F) / (E, F, D)
            if name == "w_down":
                return ("experts", "expert_mlp", "embed")
            return ("experts", "embed", "expert_mlp")
        if name == "router":
            return ("embed", None)
        if name in ("wq", "wk", "wv"):
            return ("embed", "heads")
        if name == "wo":
            return ("heads", "embed")
        if name in ("w_gate", "w_up", "w_in", "w_up2", "w_gates", "w_if"):
            return ("embed", "mlp")
        if name in ("w_down", "w_out", "w_down2"):
            return ("mlp", "embed")
        if name == "conv_w":
            return (None, "mlp")
        if name in ("w_bcdt", "a_log"):
            return ("mlp", None)
        if name == "r_gates":
            return (None, None, "mlp") if rank == 3 else (None, "mlp")
        # default: shard trailing dim on model if large
        return tuple([None] * (rank - 1) + ["mlp"])

    # ---------------------------------------------------------------- forward

    def _res_axes(self):
        """Logical axes of the residual stream (B, S, D).

        Attention families use Megatron-SP (sequence sharded on the model
        axis between blocks) — per-layer remat storage divides by TP.
        Recurrent families (ssm/hybrid) cannot shard S (time scans); they
        shard the feature dim instead.
        """
        if self.config.family in ("ssm", "hybrid"):
            return ("batch", None, "act_model")
        return ("batch", "act_seq", None)

    def _attn_tp(self) -> int:
        """TP degree of the "heads" logical axis under the active rules."""
        from repro.parallel.sharding import current_rules

        rules = current_rules()
        if rules is None or rules.mesh is None:
            return 1
        ax = rules.lookup("heads")
        return rules.mesh.shape[ax] if ax is not None else 1

    def _expand_heads_for_tp(self, q, k, v):
        """Make attention head-parallel for ANY (H, KV, TP) combination.

        §Perf iteration 2 (EXPERIMENTS.md): when H % TP != 0 (phi4 24H,
        qwen2 12H, hymba 25H on TP=16) the old fallback batch-sharded
        attention REPLICATED over the model axis — TP× redundant attention
        compute and per-layer gathers of q/k/v. Instead:

          * KV % TP != 0 → expand k/v to per-q-head layout (G=1): GQA's
            FLOPs were never shared anyway; only k/v bytes grow (by G,
            then re-sharded /TP);
          * H % TP != 0 → zero-pad heads to the next multiple of TP
            (24→32: 33% padded-head waste ≪ 16× replication).

        Returns (q, k, v, H_orig) — caller slices the output back to H.
        """
        cfg = self.config
        tp = self._attn_tp()
        B, S, H, hd = q.shape
        KV = k.shape[2]
        if tp <= 1 or (H % tp == 0 and KV % tp == 0):
            return q, k, v, H
        if KV % tp != 0:
            G = H // KV
            k = jnp.repeat(k, G, axis=2)               # (B, S, H, hd)
            v = jnp.repeat(v, G, axis=2)
        Hp = ((H + tp - 1) // tp) * tp
        if Hp != H:
            pad = [(0, 0), (0, 0), (0, Hp - H), (0, 0)]
            q = jnp.pad(q, pad)
            if k.shape[2] != Hp:
                k = jnp.pad(k, pad)
                v = jnp.pad(v, pad)
        return q, k, v, H

    def _attn_axes(self):
        """Logical axes for q and k/v inside attention (head-parallel)."""
        return (("batch", None, "heads", None),
                ("batch", None, "kv_heads", "head_dim"))

    def embed_inputs(self, params, inputs: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        if cfg.input_kind == "tokens":
            x = jnp.take(params["embed"], inputs, axis=0)
        else:
            x = inputs.astype(dtype_of(cfg.param_dtype))
        return constrain(x, self._res_axes())

    def _attention_block(
        self, bp, x, positions, *, collect_kv: bool = False,
        use_flash: bool = False, rope=None,
    ):
        cfg = self.config
        if rope is None:
            rope = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
        attn_p = bp["attn"]
        B, S, _ = x.shape
        qa, ka = self._attn_axes()
        with jax.named_scope("qkv"):
            # qkv bias rides the GEMM epilogue (fused in-kernel when packed)
            q = dense_apply(h, attn_p["wq"],
                            bias=attn_p["bq"] if cfg.qkv_bias else None)
            k = dense_apply(h, attn_p["wk"],
                            bias=attn_p["bk"] if cfg.qkv_bias else None)
            v = dense_apply(h, attn_p["wv"],
                            bias=attn_p["bv"] if cfg.qkv_bias else None)
            q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
            k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
            q = constrain(q, qa)
            k = constrain(k, ka)
            v = constrain(v, ka)
        with jax.named_scope("rope"):
            q = apply_rope_tables(q, *rope)
            k = apply_rope_tables(k, *rope)
        kv = (k, v) if collect_kv else None    # cache keeps original KV heads

        # §Perf iteration 2: head-parallel attention for any (H, KV, TP)
        qe, ke, ve, H = self._expand_heads_for_tp(q, k, v)
        qe = constrain(qe, qa)
        ke = constrain(ke, qa)                 # expanded k/v shard like q
        ve = constrain(ve, qa)
        # §Perf iteration 4 + prefill rebuild: the Pallas flash kernel on
        # the serving path (forward-only — training keeps the custom-VJP
        # XLA path). use_flash is the REQUEST; shapes the kernel cannot
        # tile (ragged S, inexact GQA ratio after TP head expansion) fall
        # back to XLA blockwise per call, so serving never crashes on an
        # unsupported prompt length.
        flash_ok = flash_prefill_supported(S, qe.shape[2], ke.shape[2])
        if use_flash and not flash_ok:
            # trace-time count: one per compiled prefill shape that asked
            # for flash and was given blockwise attention instead
            get_registry().counter("attention.flash_declined_total",
                                   seq_len=S).inc()
        with jax.named_scope("attention"):
            if use_flash and flash_ok:
                from repro.kernels import ops as kops

                out = kops.flash_attention(
                    qe, ke, ve, causal=cfg.causal,
                    window=cfg.sliding_window,
                    block_q=min(512, S), block_k=min(512, S),
                )[:, :, :H, :]
            else:
                out = blockwise_attention(
                    qe, ke, ve, causal=cfg.causal,
                    window=cfg.sliding_window, chunk=min(512, S),
                )[:, :, :H, :]
        with jax.named_scope("o_proj"):
            out = dense_apply(out.reshape(B, S, cfg.attn_dim),
                              bp["attn"]["wo"])
        return out, kv

    def _mixer_and_mlp(self, bp, x, positions, *, collect_kv: bool = False,
                       use_flash: bool = False, rope=None):
        """One full block: sequence mixer + channel mixer.

        Returns (x, aux, kv) where kv is None unless ``collect_kv`` (prefill):
        then (k, v) — plus the final mamba state for hybrid blocks.
        """
        cfg = self.config
        aux = jnp.float32(0)

        attn_out, kv = self._attention_block(bp, x, positions,
                                             collect_kv=collect_kv,
                                             use_flash=use_flash, rope=rope)
        if cfg.family == "hybrid":
            h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
            if collect_kv:
                mamba_out, mamba_state = ssm_mod.mamba_apply(
                    bp["mamba"], h, return_state=True)
                kv = kv + (mamba_state,)
            else:
                mamba_out = ssm_mod.mamba_apply(bp["mamba"], h)
            mixer = 0.5 * (attn_out + mamba_out)
        else:
            mixer = attn_out
        x = x + mixer
        x = constrain(x, self._res_axes())

        with jax.named_scope("mlp"):
            h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
            if cfg.num_experts:
                y, aux = moe_apply(
                    bp["moe"], h, top_k=cfg.moe_top_k,
                    capacity_factor=cfg.capacity_factor,
                )
            elif cfg.d_ff:
                y = ffn_apply(bp["mlp"], h, cfg.ffn_type)
            else:
                y = jnp.zeros_like(x)
        x = x + y
        return constrain(x, self._res_axes()), aux, kv

    def hidden_states(
        self, params, inputs: jnp.ndarray, positions: Optional[jnp.ndarray] = None,
        *, collect_kv: bool = False, use_flash: bool = False,
    ):
        """Full-sequence forward. Returns (hidden (B,S,D), aux, kv_stack|None).

        ``use_flash`` routes attention through the Pallas flash kernel —
        forward-only, so callers must be serving paths (prefill/encode).
        """
        cfg = self.config
        x = self.embed_inputs(params, inputs)
        B, S, _ = x.shape
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)

        if cfg.family == "ssm":
            x, states = self._xlstm_forward(params["blocks"], x)
            kv = None
            aux = jnp.float32(0)
        else:
            # rope tables are layer-invariant: build once, close over them
            rope = rope_tables(positions, cfg.head_dim, cfg.rope_theta)

            def block_fn(x, bp):
                return self._mixer_and_mlp(bp, x, positions,
                                           collect_kv=collect_kv,
                                           use_flash=use_flash, rope=rope)

            if cfg.remat != "none":
                policy = (None if cfg.remat == "full"
                          else getattr(jax.checkpoint_policies, cfg.remat))
                block_fn = jax.checkpoint(
                    block_fn, policy=policy, prevent_cse=False
                )

            def scan_body(carry, bp):
                x, aux = carry
                x, aux_i, kv = block_fn(x, bp)
                return (x, aux + aux_i), kv

            # serving path (collect_kv): unroll shallow stacks like decode
            # does — per-layer weight slices become static, so baked lane
            # tables (ServeEngine bake_weights) lower to constant-index
            # gathers. Training keeps the O(1)-HLO scan.
            unroll = min(cfg.num_layers, 4) if collect_kv else 1
            with jax.named_scope("layer_scan"):
                (x, aux), kv = jax.lax.scan(
                    scan_body, (x, jnp.float32(0)), params["blocks"],
                    unroll=unroll,
                )

        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return h, aux, kv

    # xLSTM forward: outer scan over groups, inner scan over mLSTM blocks
    def _xlstm_forward(self, blocks, x, *, return_states: bool = False):
        cfg = self.config
        H = cfg.num_heads

        def m_block_fn(x, bp):
            h = rmsnorm(bp["norm"], x, cfg.norm_eps)
            out = ssm_mod.mlstm_apply(
                {k: v for k, v in bp.items() if k != "norm"}, h, num_heads=H
            )
            return constrain(x + out, self._res_axes())

        def s_block_fn(x, bp):
            h = rmsnorm(bp["norm"], x, cfg.norm_eps)
            out = ssm_mod.slstm_apply(
                {k: v for k, v in bp.items() if k != "norm"}, h, num_heads=H
            )
            return constrain(x + out, self._res_axes())

        if cfg.remat != "none":
            m_block_fn = jax.checkpoint(m_block_fn, prevent_cse=False)
            s_block_fn = jax.checkpoint(s_block_fn, prevent_cse=False)

        def group(x, gp):
            x, _ = jax.lax.scan(lambda x_, bp: (m_block_fn(x_, bp), None),
                                x, gp["mlstm"])
            x = s_block_fn(x, gp["slstm"])
            return x, None

        x, _ = jax.lax.scan(group, x, blocks)
        return x, None

    # ------------------------------------------------------------------ loss

    def lm_logits(self, params, h: jnp.ndarray) -> jnp.ndarray:
        w = (params["embed"].T if "lm_head" not in params else params["lm_head"])
        return dense_apply(h, w)

    def train_loss(self, params, batch: Dict[str, jnp.ndarray]):
        """Chunked-CE training loss. batch: {inputs, labels}."""
        cfg = self.config
        h, aux, _ = self.hidden_states(params, batch["inputs"])
        labels = batch["labels"]
        B, S, D = h.shape
        w = (params["embed"].T if "lm_head" not in params else params["lm_head"])

        c = min(LOSS_CHUNK, S)
        n = S // c

        @jax.checkpoint
        def chunk_nll(h_c, y_c):
            logits = jnp.einsum("bcd,dv->bcv", h_c, w)
            logits = constrain(logits, ("batch", None, "vocab"))
            logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(
                logits.astype(jnp.float32), y_c[..., None], axis=-1
            )[..., 0]
            return jnp.sum(logz - gold)

        def body(tot, i):
            h_c = jax.lax.dynamic_slice_in_dim(h, i * c, c, axis=1)
            y_c = jax.lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
            return tot + chunk_nll(h_c, y_c), None

        total, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(n))
        loss = total / (B * S)
        if cfg.num_experts:
            loss = loss + MOE_AUX_COEF * aux / cfg.num_layers
        return loss

    # --------------------------------------------------------------- serving

    def cache_spec(self, seq_len: int):
        return cache_capacity(seq_len, self.config.sliding_window)

    def init_cache(self, batch: int, seq_len: int) -> Dict[str, Any]:
        """Zeroed decode cache (structure only — dry-run eval_shapes this)."""
        cfg = self.config
        if cfg.family == "ssm":
            G, per = self._xlstm_groups()
            H, hd = cfg.num_heads, cfg.head_dim
            d_inner = H * hd
            return {
                "mlstm": {
                    "C": jnp.zeros((G, per - 1, batch, H, hd, hd), jnp.float32),
                    "n": jnp.zeros((G, per - 1, batch, H, hd), jnp.float32),
                    "m": jnp.zeros((G, per - 1, batch, H), jnp.float32),
                    "conv": jnp.zeros(
                        (G, per - 1, batch, cfg.conv_kernel - 1, d_inner),
                        jnp.float32),
                },
                "slstm": {
                    k: jnp.zeros((G, batch, cfg.d_model), jnp.float32)
                    for k in ("c", "h", "n", "m")
                },
                "pos": jnp.zeros((batch,), jnp.int32),
            }

        spec = self.cache_spec(seq_len)
        dt = dtype_of(cfg.param_dtype)
        L, C = cfg.num_layers, spec.capacity
        cache: Dict[str, Any] = {
            "k": jnp.zeros((L, batch, C, cfg.num_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((L, batch, C, cfg.num_kv_heads, cfg.head_dim), dt),
            "slot_pos": jnp.full((batch, C), -1, jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
        if cfg.family == "hybrid":
            d_inner = cfg.mamba_heads * cfg.mamba_head_dim
            cache["mamba"] = {
                "h": jnp.zeros((L, batch, d_inner, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((L, batch, cfg.conv_kernel - 1, d_inner),
                                  jnp.float32),
            }
        return cache

    def cache_logical_axes(self, cache) -> Any:
        """Logical axes for the cache pytree (batch+kv_heads sharded)."""

        def axes(path, x):
            nd = len(x.shape)
            if path.startswith("k") or path.startswith("v"):
                # kv_heads shards on model when divisible; otherwise the
                # kv_dim fallback takes the model axis (shape-aware specs)
                return ("layers", "batch", None, "kv_heads", "kv_dim")
            if "mlstm" in path or "slstm" in path:
                return tuple([None] * nd)
            if "mamba" in path:
                return ("layers", "batch") + tuple([None] * (nd - 2))
            return tuple([None] * nd)

        from repro.utils.tree import tree_map_with_path_str

        return tree_map_with_path_str(axes, cache)

    def prefill(self, params, inputs: jnp.ndarray, seq_len: int,
                *, flash: Optional[bool] = None):
        """Run the prompt, build the cache, return (cache, last-token logits).

        ``flash`` routes prefill attention through the Pallas flash kernel
        (``kernels/flash_attention.py``): None = auto (on for real TPU
        backends, off in interpret mode), True/False = force. Shapes the
        kernel cannot tile fall back to XLA blockwise attention per block
        — the request is an upper bound, never a crash.
        """
        cfg = self.config
        B = inputs.shape[0]
        S = inputs.shape[1]

        if cfg.family == "ssm":
            # one forward pass, collecting the final recurrent states
            cache, x = self._xlstm_prefill(params, inputs)
            h = rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
            logits = self.lm_logits(params, h)
            return cache, logits

        # serving path: the Pallas flash kernel engages wherever kernels
        # run compiled (interpret-mode flash is a correctness tool, not a
        # fast path)
        use_flash = _kernels_compiled() if flash is None else bool(flash)
        h, _, kv = self.hidden_states(params, inputs, collect_kv=True,
                                      use_flash=use_flash)
        cache = self.init_cache(B, seq_len)
        spec = self.cache_spec(seq_len)
        if cfg.family == "hybrid":
            k_all, v_all, mamba_states = kv     # states stacked (L, ...)
        else:
            k_all, v_all = kv                   # (L, B, S, KV, hd)
        C = spec.capacity
        if spec.ring:
            keep = min(C, S)
            sl = (jnp.arange(S - keep, S)) % C
            cache["k"] = cache["k"].at[:, :, sl].set(k_all[:, :, S - keep:])
            cache["v"] = cache["v"].at[:, :, sl].set(v_all[:, :, S - keep:])
            cache["slot_pos"] = cache["slot_pos"].at[:, sl].set(
                jnp.arange(S - keep, S, dtype=jnp.int32)[None, :]
            )
        else:
            cache["k"] = cache["k"].at[:, :, :S].set(k_all)
            cache["v"] = cache["v"].at[:, :, :S].set(v_all)
            cache["slot_pos"] = cache["slot_pos"].at[:, :S].set(
                jnp.arange(S, dtype=jnp.int32)[None, :]
            )
        cache["pos"] = jnp.full((B,), S, jnp.int32)
        if cfg.family == "hybrid":
            cache["mamba"] = mamba_states
        logits = self.lm_logits(params, h[:, -1:, :])
        return cache, logits

    def prefill_into_slot(self, params, cache, prompt: jnp.ndarray,
                          slot, *, flash: Optional[bool] = None):
        """Prefill ONE prompt into ONE free slot of a LIVE decode cache.

        ``prompt``: (1, S) ids (or (1, S, D) embeddings); ``slot``: scalar
        int32 batch index — traced, so one compiled program per prompt
        length serves EVERY slot. The prompt runs exactly like a solo
        ``prefill`` (positions 0..S-1, no batch-mates, no padding — the
        hidden states are bit-identical to serving the request alone),
        and only the slot's rows of the cache are touched: its k/v rows,
        its ``slot_pos`` row (reset via ``slot_prompt_rows`` — fresh
        positions where written, -1 elsewhere, so a retired occupant's
        stale KV is masked out, not read), and its ``pos`` entry. Every
        other slot's buffers pass through UNTOUCHED, which is what makes
        mid-decode admission safe for the live requests around it.
        Returns ``(cache, last-token logits (1, 1, V))``.
        """
        cfg = self.config
        if cfg.family == "ssm":
            raise NotImplementedError(
                "prefill_into_slot needs a KV-cache family; xLSTM "
                "recurrent-state slot admission is not implemented"
            )
        S = prompt.shape[1]
        use_flash = _kernels_compiled() if flash is None else bool(flash)
        h, _, kv = self.hidden_states(params, prompt, collect_kv=True,
                                      use_flash=use_flash)
        if cfg.family == "hybrid":
            k_all, v_all, mamba_states = kv     # (L, 1, S, KV, hd)
        else:
            k_all, v_all = kv
        C = cache["k"].shape[2]
        # mirror decode_step's ring rule: the buffer rings iff a sliding
        # window bounds its capacity
        ring = cfg.sliding_window is not None and C <= cfg.sliding_window
        rows, keep, sp_row = slot_prompt_rows(C, S, ring)
        slot = jnp.asarray(slot, jnp.int32)
        kd = cache["k"].dtype
        cache = dict(cache)
        with jax.named_scope("kv_write"):
            if ring:
                cache["k"] = cache["k"].at[:, slot, rows].set(
                    k_all[:, 0, S - keep:].astype(kd))
                cache["v"] = cache["v"].at[:, slot, rows].set(
                    v_all[:, 0, S - keep:].astype(kd))
            else:
                z = jnp.int32(0)
                cache["k"] = jax.lax.dynamic_update_slice(
                    cache["k"], k_all.astype(kd), (z, slot, z, z, z))
                cache["v"] = jax.lax.dynamic_update_slice(
                    cache["v"], v_all.astype(kd), (z, slot, z, z, z))
            cache["slot_pos"] = jax.lax.dynamic_update_slice(
                cache["slot_pos"], sp_row[None, :], (slot, jnp.int32(0)))
            cache["pos"] = jax.lax.dynamic_update_slice(
                cache["pos"], jnp.full((1,), S, jnp.int32), (slot,))
        if cfg.family == "hybrid":
            cache["mamba"] = jax.tree.map(
                lambda buf, st: buf.at[:, slot].set(
                    st[:, 0].astype(buf.dtype)),
                cache["mamba"], mamba_states)
        with jax.named_scope("head"):
            logits = self.lm_logits(params, h[:, -1:, :])
        return cache, logits

    def _xlstm_prefill(self, params, inputs):
        cfg = self.config
        x = self.embed_inputs(params, inputs)
        B = x.shape[0]
        H, hd = cfg.num_heads, cfg.head_dim
        d_inner = H * hd
        G, per = self._xlstm_groups()

        def m_block(carry, bp):
            x = carry
            h = rmsnorm(bp["norm"], x, cfg.norm_eps)
            p = {k: v for k, v in bp.items() if k != "norm"}
            out, st = ssm_mod.mlstm_apply(p, h, num_heads=H, return_state=True)
            return x + out, st

        def s_block(x, bp):
            h = rmsnorm(bp["norm"], x, cfg.norm_eps)
            p = {k: v for k, v in bp.items() if k != "norm"}
            out, st = ssm_mod.slstm_apply(p, h, num_heads=H, return_state=True)
            return x + out, st

        def group(x, gp):
            x, mst = jax.lax.scan(m_block, x, gp["mlstm"])
            x, sst = s_block(x, gp["slstm"])
            return x, {"mlstm": mst, "slstm": sst}

        x, states = jax.lax.scan(group, x, params["blocks"])
        states["pos"] = jnp.full((B,), inputs.shape[1], jnp.int32)
        return states, x

    # ------------------------------------------------------------ decode step

    def decode_step(self, params, cache: Dict[str, Any], tokens: jnp.ndarray):
        """One decode step. tokens: (B, 1) ids or (B, 1, D) embeddings.

        The layer scan runs over ``(blocks, layer index)`` and carries the
        stacked (L, B, C, KV, hd) K/V buffers: layer ``l`` writes its new
        row at ``[l, b, slot]`` (``cache_insert``, one row per layer,
        in place in the carried stack) and attends over layer ``l`` of the
        same buffers, so no layer's slab is sliced out of the stack as a
        scan input or rebuilt as a scan output. The hybrid family's mamba
        state, a few KB a layer, is scanned over as input and output.
        """
        cfg = self.config
        if cfg.family == "ssm":
            return self._xlstm_decode(params, cache, tokens)

        x = self.embed_inputs(params, tokens)          # (B, 1, D)
        B = x.shape[0]
        pos = cache["pos"]                              # (B,)
        ring = self._cache_ring(cache)

        # the new position's slot, and so the mask row it leaves, is the
        # same in every layer: written once, outside the layer scan
        _, rows = chunk_rows(pos, 1, cache["k"].shape[2], ring)
        slot_pos = cache["slot_pos"].at[jnp.arange(B), rows[:, 0]].set(pos)
        # rope tables depend only on pos — compute once, reuse per layer
        with jax.named_scope("rope"):
            r_sin, r_cos = rope_tables(pos[:, None], cfg.head_dim,
                                       cfg.rope_theta)

        # named scopes put each part of the step into the op_name of its
        # device ops, so a profile attributes device time to them
        def block_step(carry, xs):
            x, k_all, v_all = carry
            if cfg.family == "hybrid":
                bp, layer, mst = xs
            else:
                bp, layer = xs
                mst = None
            h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
            attn_p = bp["attn"]
            with jax.named_scope("qkv"):
                q = dense_apply(h, attn_p["wq"],
                                bias=attn_p["bq"] if cfg.qkv_bias else None)
                k = dense_apply(h, attn_p["wk"],
                                bias=attn_p["bk"] if cfg.qkv_bias else None)
                v = dense_apply(h, attn_p["wv"],
                                bias=attn_p["bv"] if cfg.qkv_bias else None)
                q = q.reshape(B, 1, cfg.num_heads, cfg.head_dim)
                k = k.reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
                v = v.reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
            with jax.named_scope("rope"):
                q = apply_rope_tables(q, r_sin, r_cos)
                k = apply_rope_tables(k, r_sin, r_cos)
            with jax.named_scope("kv_write"):
                # the slot_pos it returns is the one written above
                k_all, v_all, _ = cache_insert(k_all, v_all, slot_pos, k, v,
                                               (layer, pos), ring=ring)
            with jax.named_scope("attention"):
                attn = decode_attention(
                    q, k_all[layer], v_all[layer], slot_pos, pos,
                    window=cfg.sliding_window,
                )
            with jax.named_scope("o_proj"):
                attn = dense_apply(attn.reshape(B, 1, cfg.attn_dim),
                                   bp["attn"]["wo"])
            if cfg.family == "hybrid":
                m_out, new_mst = ssm_mod.mamba_step(
                    bp["mamba"], h[:, 0, :], mst)
                mixer = 0.5 * (attn + m_out[:, None, :])
            else:
                new_mst = None
                mixer = attn
            x = x + mixer
            with jax.named_scope("mlp"):
                h2 = rmsnorm(bp["norm2"], x, cfg.norm_eps)
                if cfg.num_experts:
                    y, _ = moe_apply(bp["moe"], h2, top_k=cfg.moe_top_k,
                                     capacity_factor=cfg.capacity_factor)
                elif cfg.d_ff:
                    y = ffn_apply(bp["mlp"], h2, cfg.ffn_type)
                else:
                    y = jnp.zeros_like(x)
            x = x + y
            return (x, k_all, v_all), new_mst

        layers = jnp.arange(cfg.num_layers)
        if cfg.family == "hybrid":
            xs = (params["blocks"], layers, cache["mamba"])
        else:
            xs = (params["blocks"], layers)
        # shallow stacks: unroll the layer scan (no while-loop overhead at
        # decode); deep stacks keep the O(1)-HLO scan
        with jax.named_scope("layer_scan"):
            (x, new_k, new_v), new_mamba = jax.lax.scan(
                block_step, (x, cache["k"], cache["v"]), xs,
                unroll=min(cfg.num_layers, 4))
        if cfg.family == "hybrid":
            cache = {**cache, "mamba": new_mamba}
        cache = {**cache, "k": new_k, "v": new_v, "slot_pos": slot_pos,
                 "pos": pos + 1}

        with jax.named_scope("head"):
            h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
            logits = self.lm_logits(params, h)
        return cache, logits

    def decode_many(self, params, cache, tokens: jnp.ndarray,
                    num_steps: int, sampler=None, unroll: int = 4,
                    keys: Optional[jnp.ndarray] = None,
                    with_flags: bool = False):
        """Device-resident multi-token decode: one ``lax.scan`` over steps.

        Samples on-device after every step and feeds the token back in, so
        a whole ``num_steps`` block costs ONE XLA dispatch and ONE host
        transfer instead of one of each per token. The KV cache lives in
        the scan carry — XLA reuses (donates) its buffers across steps
        instead of round-tripping them to the host — and, inside each
        step, in the layer scan's carry (``decode_step``), updated one row
        per layer in place.

        tokens: (B, 1) int32 — the first token of the block (e.g. sampled
        from the prefill logits). ``sampler``: jit-compatible
        ``logits (B, 1, V) -> (B, 1) int32`` (default: greedy argmax).
        ``keys``: optional per-step PRNG keys, leading dim ``num_steps`` —
        when given the sampler is called as ``sampler(logits, key)`` so
        stochastic samplers (``temperature_sample``) draw a fresh key
        every step without leaving the scan. ``unroll`` trades
        compiled-code size for per-step while-loop overhead (any
        ``num_steps`` is fine, jax handles remainders).
        Returns (final cache, tokens (B, num_steps)) where column 0 is the
        token sampled AFTER feeding ``tokens`` (i.e. the continuation).

        ``with_flags=True`` additionally returns per-step per-row health
        flags (B, num_steps) bool — True where that row's logits for that
        step were all finite. The flags are a pure OBSERVATION of the
        logits already computed (token math is untouched, so healthy rows
        stay bit-identical with or without flags); the serving layer uses
        them to quarantine a NaN-poisoned slot at the exact step the
        poison surfaced.
        """
        if sampler is None:
            from repro.serve.sampler import greedy_sample
            sampler = greedy_sample

        def step(carry, key):
            cache, tok = carry
            cache, logits = self.decode_step(params, cache, tok)
            with jax.named_scope("sample"):
                nxt = sampler(logits) if key is None else sampler(logits,
                                                                  key)
            if with_flags:
                with jax.named_scope("health"):
                    ok = jnp.isfinite(logits).all(axis=(-2, -1))  # (B,)
                return (cache, nxt), (nxt, ok)
            return (cache, nxt), nxt

        (cache, _), ys = jax.lax.scan(
            step, (cache, tokens), xs=keys, length=num_steps,
            unroll=min(unroll, num_steps),
        )
        if with_flags:
            toks, flags = ys
            return (cache, jnp.swapaxes(toks[..., 0], 0, 1),
                    jnp.swapaxes(flags, 0, 1))          # (B, num_steps)
        return cache, jnp.swapaxes(ys[..., 0], 0, 1)     # (B, num_steps)

    # ------------------------------------------------- chunked verify path

    def _require_kv_family(self, what: str) -> None:
        if self.config.family in ("ssm", "hybrid"):
            raise NotImplementedError(
                f"{what} needs per-position KV rows to rewind; "
                f"family={self.config.family!r} carries recurrent state "
                "(rollback would need per-step state stacking) — serve it "
                "without speculation"
            )

    def _cache_ring(self, cache) -> bool:
        """Ring iff a sliding window bounds the capacity C (the rule of
        ``decode_step`` and ``verify_chunk``)."""
        C = cache["k"].shape[2]
        return self.config.sliding_window is not None and \
            C <= self.config.sliding_window

    def verify_chunk(self, params, cache: Dict[str, Any],
                     tokens: jnp.ndarray):
        """K-token chunked decode: per-position logits in ONE dispatch.

        ``tokens``: (B, K) ids (or (B, K, D) embeddings) — the last
        committed token followed by K-1 draft continuations. Every batch
        row runs at ITS OWN positions ``pos[b] .. pos[b]+K-1`` (per-row
        rope, per-row causal horizon — the same per-slot geometry the
        continuous engine rests on). The chunk's k/v are inserted into the
        cache FIRST (``cache_insert_chunk``), then ``chunk_attention``
        masks by ``slot_pos <= q_pos`` so intra-chunk causality falls out
        of the cache mask. Returns ``(cache, logits (B, K, V))`` with
        ``pos`` advanced by K — callers that may reject a suffix take a
        ``cache_snapshot`` BEFORE the call and ``cache_rollback`` after.

        Compared to K ``decode_step`` calls this is one dispatch whose
        GEMMs run at M = B*K instead of K sequential M = B dispatches —
        the verifier-side half of the speculative hot path.
        """
        cfg = self.config
        self._require_kv_family("verify_chunk")
        x = self.embed_inputs(params, tokens)           # (B, K, D)
        B, K = x.shape[0], x.shape[1]
        pos = cache["pos"]                              # (B,)
        C = cache["k"].shape[2]
        ring = self._cache_ring(cache)
        if ring and K > C:
            raise ValueError(
                f"verify chunk of {K} tokens exceeds the ring cache's "
                f"window capacity {C} — lower draft_k"
            )
        q_pos, rows = chunk_rows(pos, K, C, ring)       # (B, K) positions
        r_sin, r_cos = rope_tables(q_pos, cfg.head_dim, cfg.rope_theta)
        # slot_pos is layer-invariant: the post-chunk row set is one
        # scatter, computed ONCE — layers must all mask against the same
        # (pre-chunk for ring, post-insert for non-ring) view, never a
        # mid-scan mixture of another layer's inserts and their own bytes
        bidx = jnp.arange(x.shape[0])[:, None]
        sp_new = cache["slot_pos"].at[bidx, rows].set(q_pos)
        sp_attn = (jnp.concatenate([cache["slot_pos"], q_pos], axis=1)
                   if ring else sp_new)

        def block_step(x, xs):
            bp, kc, vc = xs
            h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
            attn_p = bp["attn"]
            q = dense_apply(h, attn_p["wq"],
                            bias=attn_p["bq"] if cfg.qkv_bias else None)
            k = dense_apply(h, attn_p["wk"],
                            bias=attn_p["bk"] if cfg.qkv_bias else None)
            v = dense_apply(h, attn_p["wv"],
                            bias=attn_p["bv"] if cfg.qkv_bias else None)
            q = q.reshape(B, K, cfg.num_heads, cfg.head_dim)
            k = k.reshape(B, K, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(B, K, cfg.num_kv_heads, cfg.head_dim)
            q = apply_rope_tables(q, r_sin, r_cos)
            k = apply_rope_tables(k, r_sin, r_cos)

            if ring:
                # two-part attention: the chunk's keys ride ALONGSIDE the
                # unmodified cache. Inserting first would overwrite window
                # history the chunk's earlier queries still see (a ring
                # insert at pos+j evicts pos+j-W, which is inside query
                # pos+i's window whenever i < j) — position masks over
                # the concatenated slots give exact sequential semantics.
                k_ext = jnp.concatenate([kc, k.astype(kc.dtype)], axis=1)
                v_ext = jnp.concatenate([vc, v.astype(vc.dtype)], axis=1)
                attn = chunk_attention(q, k_ext, v_ext, sp_attn, q_pos,
                                       window=cfg.sliding_window)
                kc = kc.at[bidx, rows].set(k.astype(kc.dtype))
                vc = vc.at[bidx, rows].set(v.astype(vc.dtype))
            else:
                # fresh slots only (slot index == position): insert first,
                # then one attention over the cache — intra-chunk
                # causality falls out of the slot_pos <= q_pos mask
                kc = kc.at[bidx, rows].set(k.astype(kc.dtype))
                vc = vc.at[bidx, rows].set(v.astype(vc.dtype))
                attn = chunk_attention(q, kc, vc, sp_attn, q_pos,
                                       window=cfg.sliding_window)
            attn = dense_apply(attn.reshape(B, K, cfg.attn_dim),
                               bp["attn"]["wo"])
            x = x + attn
            h2 = rmsnorm(bp["norm2"], x, cfg.norm_eps)
            if cfg.num_experts:
                y, _ = moe_apply(bp["moe"], h2, top_k=cfg.moe_top_k,
                                 capacity_factor=cfg.capacity_factor)
            elif cfg.d_ff:
                y = ffn_apply(bp["mlp"], h2, cfg.ffn_type)
            else:
                y = jnp.zeros_like(x)
            x = x + y
            return x, (kc, vc)

        x, (new_k, new_v) = jax.lax.scan(
            block_step, x, (params["blocks"], cache["k"], cache["v"]),
            unroll=min(cfg.num_layers, 4))
        cache = {**cache, "k": new_k, "v": new_v, "slot_pos": sp_new,
                 "pos": pos + K}
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return cache, self.lm_logits(params, h)

    def cache_snapshot(self, cache: Dict[str, Any], K: int) -> Dict[str, Any]:
        """Snapshot the cache rows the NEXT ``K`` inserted positions will
        overwrite, so ``cache_rollback`` can rewind exactly.

        Non-ring caches would survive a rewind with masking alone (stale
        future rows are causally masked and re-inserted in place), but
        ring caches cannot: a rejected insert that wrapped has OVERWRITTEN
        live window history, and only restoring the saved rows brings it
        back. Saving both geometries' rows makes rollback produce a cache
        BIT-IDENTICAL to one that never saw the rejected tokens — the
        invariant the speculative engine's lockstep tests assert.
        """
        self._require_kv_family("cache_snapshot")
        pos = cache["pos"]
        C = cache["k"].shape[2]
        idx, rows = chunk_rows(pos, K, C, self._cache_ring(cache))
        grows = jnp.minimum(rows, C - 1)      # clamp gathers; scatters drop
        b = jnp.arange(pos.shape[0])[:, None]
        return {
            "k": cache["k"][:, b, grows],          # (L, B, K, KV, hd)
            "v": cache["v"][:, b, grows],
            "slot_pos": cache["slot_pos"][b, grows],   # (B, K)
            "rows": rows,
            "idx": idx,
            "pos": pos,
        }

    def cache_rollback(self, cache: Dict[str, Any], snap: Dict[str, Any],
                       keep: jnp.ndarray) -> Dict[str, Any]:
        """Rewind a cache to ``snap``'s position plus ``keep`` accepted
        inserts per row.

        ``keep``: (B,) int32 in ``[0, K]`` — row ``b`` keeps its first
        ``keep[b]`` post-snapshot positions; everything after is restored
        from the snapshot (k/v bytes AND ``slot_pos``) and ``pos`` rewinds
        to ``snap["pos"] + keep``. Per-row ``keep`` is what lets one
        speculative round accept different prefix lengths per batch row.
        """
        self._require_kv_family("cache_rollback")
        K = snap["rows"].shape[1]
        rows = snap["rows"]
        grows = jnp.minimum(rows, cache["k"].shape[2] - 1)
        b = jnp.arange(rows.shape[0])[:, None]
        rej = jnp.arange(K, dtype=jnp.int32)[None, :] >= keep[:, None]
        sel = rej[None, :, :, None, None]
        new_k = cache["k"].at[:, b, rows].set(
            jnp.where(sel, snap["k"], cache["k"][:, b, grows]))
        new_v = cache["v"].at[:, b, rows].set(
            jnp.where(sel, snap["v"], cache["v"][:, b, grows]))
        new_sp = cache["slot_pos"].at[b, rows].set(
            jnp.where(rej, snap["slot_pos"], snap["idx"]))
        return {**cache, "k": new_k, "v": new_v, "slot_pos": new_sp,
                "pos": snap["pos"] + keep}

    def _xlstm_decode(self, params, cache, tokens):
        cfg = self.config
        H = cfg.num_heads
        x = self.embed_inputs(params, tokens)[:, 0, :]  # (B, D)

        def m_step(carry, xs):
            x = carry
            bp, st = xs
            h = rmsnorm(bp["norm"], x[:, None, :], cfg.norm_eps)[:, 0, :]
            p = {k: v for k, v in bp.items() if k != "norm"}
            out, st = ssm_mod.mlstm_step(p, h, st, num_heads=H)
            return x + out, st

        def group(carry, xs):
            x = carry
            gp, gc = xs
            x, mst = jax.lax.scan(m_step, x, (gp["mlstm"], gc["mlstm"]))
            h = rmsnorm(gp["slstm"]["norm"], x[:, None, :], cfg.norm_eps)[:, 0, :]
            p = {k: v for k, v in gp["slstm"].items() if k != "norm"}
            out, sst = ssm_mod.slstm_step(p, h, gc["slstm"], num_heads=H)
            return x + out, {"mlstm": mst, "slstm": sst}

        states = {k: cache[k] for k in ("mlstm", "slstm")}
        x, new_states = jax.lax.scan(group, x, (params["blocks"], states))
        new_states["pos"] = cache["pos"] + 1

        h = rmsnorm(params["final_norm"], x[:, None, :], cfg.norm_eps)
        logits = self.lm_logits(params, h)
        return new_states, logits

"""The autotuner + plan cache (sparse/tune.py) and the prefill rebuild.

Covers the ISSUE-3 acceptance surface:
  * Plan strings round-trip (they live in PackedTensor.meta and the JSON
    checkpoint manifest — flat strings by contract);
  * every candidate execution plan (gather vs Pallas grids, both grid
    orders, block sizes) computes BIT-IDENTICAL results — tuning can only
    change latency, never tokens;
  * tuned plans persist through PrunedArtifact.save()/.load() and tuned
    vs untuned dispatch is bit-identical;
  * legacy flat-layout tile_pattern artifacts (packed before the blocked
    (nb, Kp, bp) refactor) load and dispatch identically to the blocked
    layout at both decode and prefill M (the registry compat path);
  * flash-attention prefill ≡ XLA blockwise attention at serve shapes
    (causal, batch > 1, bfloat16, sliding window), and the serve path's
    shape gate routes correctly;
  * ServeEngine.generate buckets by prompt length but returns results in
    the original request order with unchanged tokens.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import DEFAULT_EXCLUDE, PruneConfig, greedy_prune
from repro.core.schemes import LayerSpec
from repro.models import build_model
from repro.models.attention import blockwise_attention, flash_prefill_supported
from repro.serve import Request, ServeEngine
from repro.sparse import PrunedArtifact, dispatch_matmul, handler_for
from repro.sparse import tune
from repro.sparse.packed import PackedTensor, is_packed


def _rand(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def _tile_pt(seed=0, shape=(256, 128), block_p=64):
    spec = LayerSpec(scheme="tile_pattern", tile_block_p=block_p,
                     tile_group_q=8, tile_keep=4)
    w = spec.project(_rand(seed, shape))
    return handler_for("tile_pattern").pack(w, spec), w


class TestPlan:
    def test_roundtrip(self):
        for p in (tune.Plan("gather"), tune.Plan("xla"),
                  tune.Plan("pallas", block_m=256),
                  tune.Plan("pallas", block_m=128, block_k=512, grid="pm")):
            assert tune.Plan.from_str(p.to_str()) == p

    def test_m_bucket(self):
        assert tune.m_bucket(8) == 32          # decode floors at small_m
        assert tune.m_bucket(32) == 32
        assert tune.m_bucket(33) == 64
        assert tune.m_bucket(256) == 256
        assert tune.m_bucket(257) == 512

    def test_interpret_candidates_are_xla_only(self):
        pt, _ = _tile_pt()
        interp = tune.candidate_plans(pt, "matmul", 256, True)
        assert interp and all(c.impl.startswith("gather") for c in interp)
        full = tune.candidate_plans(pt, "matmul", 256, False)
        assert any(c.impl == "pallas" for c in full)


class TestCandidateBitIdentity:
    """Every plan is the same math: outputs must match BITWISE."""

    @pytest.mark.parametrize("M", [96, 256])
    def test_tile_pattern(self, M):
        pt, _ = _tile_pt()
        h = handler_for("tile_pattern")
        x = _rand(1, (M, 256))
        outs = {}
        for cand in tune.candidate_plans(pt, "matmul", M, False):
            fn = jax.jit(h.plan(pt, M, False, None, True, exec_plan=cand))
            outs[cand.to_str()] = np.asarray(fn(x, pt, None))
        ref = outs[tune.Plan("gather").to_str()]
        for name, out in outs.items():
            assert np.array_equal(ref, out), f"plan {name} diverged"

    def test_column(self):
        spec = LayerSpec(scheme="column", alpha=0.25)
        w = spec.project(_rand(2, (128, 96)))
        h = handler_for("column")
        pt = h.pack(w, spec)
        x = _rand(3, (200, 128))
        outs = {}
        for cand in tune.candidate_plans(pt, "matmul", 200, False):
            fn = jax.jit(h.plan(pt, 200, False, None, True, exec_plan=cand))
            outs[cand.to_str()] = np.asarray(fn(x, pt, None))
        ref = outs[tune.Plan("gather").to_str()]
        for name, out in outs.items():
            assert np.array_equal(ref, out), f"plan {name} diverged"

    def test_conv_gemm(self):
        from repro.sparse.registry import conv_gemm_runner

        spec = LayerSpec(scheme="pattern_shared", alpha=0.4,
                         conv_shape=(16, 8, 3, 3))
        w4 = spec.project(_rand(4, (16, 8, 3, 3)))
        pt = handler_for("pattern_shared").pack(w4, spec)
        xg = _rand(5, (64, pt.buf("w_packed").shape[0]))
        w = pt.buf("w_packed")
        outs = {}
        for cand in tune.candidate_plans(pt, "conv", 64, False):
            fn = jax.jit(conv_gemm_runner(pt, cand, interpret=True))
            outs[cand.to_str()] = np.asarray(fn(xg, w))
        ref = outs["xla"]
        for name, out in outs.items():
            assert np.array_equal(ref, out), f"conv plan {name} diverged"


class TestTunerPersistence:
    @pytest.fixture(scope="class")
    def artifact(self):
        cfg = ModelConfig(name="tiny", family="dense", num_layers=2,
                          d_model=128, num_heads=4, num_kv_heads=2,
                          head_dim=32, d_ff=256, vocab_size=512,
                          param_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pcfg = PruneConfig(
            scheme="tile_pattern", exclude=tuple(DEFAULT_EXCLUDE),
            overrides={".*": {"tile_block_p": 32, "tile_group_q": 8,
                              "tile_keep": 4}})
        art = greedy_prune(params, pcfg).to_artifact(arch="tiny")
        return cfg, model, art

    def test_plan_cache_roundtrips_save_load(self, artifact, tmp_path):
        cfg, model, art = artifact
        tuned = art.pack(tune_for=(4, 64), tune_iters=1)
        plans = tune.describe_plans(tuned.packed)
        assert plans, "tuner wrote no plans into any PackedTensor meta"
        for leaf_plans in plans.values():
            assert "plan:matmul:m32" in leaf_plans
            assert "plan:matmul:m64" in leaf_plans
        assert tuned.meta.get("tuned_plans"), "search report not in meta"

        d = os.path.join(tmp_path, "art")
        tuned.save(d)
        loaded = PrunedArtifact.load(d)
        assert tune.describe_plans(loaded.packed) == plans
        assert loaded.meta["tuned_plans"] == tuned.meta["tuned_plans"]

    def test_plans_gated_by_execution_mode(self):
        """Plans tuned in interpret mode must not pin a compiled (TPU)
        backend to them — resolve() consults meta only when plan_mode
        matches, otherwise the per-backend heuristic default applies."""
        pt, _ = _tile_pt()
        tree, _ = tune.tune_packed_tree({"w": pt}, (64,), interpret=True,
                                        iters=1)
        tuned = tree["w"]
        assert tuned.meta_dict["plan_mode"] == "interpret"
        assert tune.resolve(tuned, "matmul", 64, interpret=True) is not None
        assert tune.resolve(tuned, "matmul", 64, interpret=False) is None

    def test_tuned_untuned_bit_identical(self, artifact):
        cfg, model, art = artifact
        untuned = art.pack()
        tuned = art.pack(tune_for=(4, 64), tune_iters=1)

        def packed_leaves(a):
            return [l for l in jax.tree.leaves(a.packed, is_leaf=is_packed)
                    if is_packed(l) and not l.stacked]

        for pt_u, pt_t in zip(packed_leaves(untuned), packed_leaves(tuned)):
            x = _rand(7, (64, pt_u.shape[-2]))
            yu = np.asarray(dispatch_matmul(x, pt_u))
            yt = np.asarray(dispatch_matmul(x, pt_t))
            assert np.array_equal(yu, yt)

    def test_tuned_artifact_serves_token_identical(self, artifact):
        cfg, model, art = artifact
        reqs = [Request(uid=i, prompt=jnp.arange(6 + i) % cfg.vocab_size,
                        max_new_tokens=5) for i in range(3)]
        plain = ServeEngine(model, art.pack(), batch_size=4, max_seq_len=64,
                            packed=True)
        tuned = ServeEngine(model, art.pack(tune_for=(4, 4 * 11),
                                            tune_iters=1),
                            batch_size=4, max_seq_len=64, packed=True)
        assert ([r.tokens for r in plain.generate(reqs)]
                == [r.tokens for r in tuned.generate(reqs)])


class TestLegacyFlatLayout:
    """Artifacts packed before the blocked-(nb, Kp, bp) layout still load
    and dispatch identically (the ``_tile_wpb`` compat path)."""

    def _legacy_pt(self, w):
        from repro.kernels.pattern_gemm import pack_tile_pattern

        wp, li = pack_tile_pattern(w, block_p=64, group_q=8, keep=4)
        # pre-refactor meta: flat (Kp, P) buffer, no w_ndim key
        return PackedTensor(
            "tile_pattern", tuple(w.shape), ("w_packed", "lane_idx"),
            (wp, li), (("block_p", 64), ("group_q", 8), ("keep", 4)))

    def test_flat_manifest_dispatch_parity(self, tmp_path):
        from repro.checkpoint import load_pytree, save_pytree

        pt_blocked, w = _tile_pt(seed=11)
        legacy = self._legacy_pt(w)
        assert legacy.canonical_w_ndim == 2 and pt_blocked.canonical_w_ndim == 3

        d = os.path.join(tmp_path, "legacy")
        save_pytree(d, {"w": legacy})
        loaded = load_pytree(d)["w"]
        assert is_packed(loaded) and loaded.canonical_w_ndim == 2

        h = handler_for("tile_pattern")
        # exact dense reconstruction through the flat-layout path
        assert np.array_equal(np.asarray(h.to_dense(loaded)), np.asarray(w))
        for M in (4, 96):                       # decode and prefill regimes
            x = _rand(12, (M, 256))
            y_flat = np.asarray(dispatch_matmul(x, loaded))
            y_blocked = np.asarray(dispatch_matmul(x, pt_blocked))
            assert np.array_equal(y_flat, y_blocked)

    def test_flat_layout_pallas_plan_parity(self):
        pt_blocked, w = _tile_pt(seed=13)
        legacy = self._legacy_pt(w)
        h = handler_for("tile_pattern")
        x = _rand(14, (128, 256))
        cand = tune.Plan("pallas", block_m=128)
        y_flat = jax.jit(h.plan(legacy, 128, False, None, True,
                                exec_plan=cand))(x, legacy, None)
        y_blocked = jax.jit(h.plan(pt_blocked, 128, False, None, True,
                                   exec_plan=cand))(x, pt_blocked, None)
        assert np.array_equal(np.asarray(y_flat), np.asarray(y_blocked))


class TestFlashPrefill:
    """Pallas flash attention ≡ XLA blockwise at serve shapes."""

    @pytest.mark.parametrize("window", [None, 32])
    def test_flash_matches_blockwise_bf16_batch(self, window):
        from repro.kernels import ops as kops

        B, S, H, KV, hd = 2, 64, 4, 2, 16
        q = _rand(20, (B, S, H, hd), jnp.bfloat16)
        k = _rand(21, (B, S, KV, hd), jnp.bfloat16)
        v = _rand(22, (B, S, KV, hd), jnp.bfloat16)
        y_flash = kops.flash_attention(q, k, v, causal=True, window=window,
                                       block_q=32, block_k=32)
        y_block = blockwise_attention(q, k, v, causal=True, window=window,
                                      chunk=32)
        np.testing.assert_allclose(
            np.asarray(y_flash, np.float32), np.asarray(y_block, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_supported_predicate(self):
        assert flash_prefill_supported(64, 4, 2)         # S <= block
        assert flash_prefill_supported(1024, 4, 2)       # S % 512 == 0
        assert not flash_prefill_supported(600, 4, 2)    # ragged S
        assert not flash_prefill_supported(37, 4, 2)     # off the 8-row tile
        assert not flash_prefill_supported(64, 5, 2)     # inexact GQA
        assert not flash_prefill_supported(0, 4, 2)

    def test_prefill_flash_matches_blockwise_logits(self):
        cfg = ModelConfig(name="tiny", family="dense", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          head_dim=16, d_ff=128, vocab_size=128,
                          param_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                     cfg.vocab_size)
        _, logits_flash = model.prefill(params, prompts, 32, flash=True)
        _, logits_block = model.prefill(params, prompts, 32, flash=False)
        np.testing.assert_allclose(np.asarray(logits_flash),
                                   np.asarray(logits_block),
                                   rtol=2e-4, atol=2e-4)

    def test_declined_shape_is_counted(self):
        from repro.runtime.telemetry import get_registry

        cfg = ModelConfig(name="tiny", family="dense", num_layers=1,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          head_dim=16, d_ff=128, vocab_size=128,
                          param_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        declined = get_registry().counter("attention.flash_declined_total",
                                          seq_len=37)
        before = declined.value
        prompt = jnp.arange(37)[None] % cfg.vocab_size
        _, logits = model.prefill(params, prompt, 40, flash=True)
        assert declined.value == before + 1       # one trace, blockwise
        _, ref = model.prefill(params, prompt, 40, flash=False)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))


class TestGenerateBucketing:
    def test_results_in_request_order_and_token_identical(self):
        cfg = ModelConfig(name="tiny", family="dense", num_layers=2,
                          d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                          vocab_size=64, param_dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, batch_size=2, max_seq_len=64)
        # interleaved long/short prompts: bucketing reorders serving (the
        # sorted chunks here are (3,3), (9,9), (9)), but the results must
        # come back in the original request order anyway
        lens = [9, 3, 9, 3, 9]
        reqs = [Request(uid=100 + i, prompt=jnp.arange(n) % cfg.vocab_size,
                        max_new_tokens=4) for i, n in enumerate(lens)]
        out = eng.generate(reqs)
        assert [r.uid for r in out] == [100 + i for i in range(len(lens))]

        # bucketing made every chunk pad-free (equal lengths within each
        # chunk), so tokens match serving each request alone — the engine
        # left-pads SHORTER prompts in a mixed chunk with zero tokens the
        # model attends to, which is exactly the distortion (and prefill
        # waste) length-bucketing removes
        solo = ServeEngine(model, params, batch_size=1, max_seq_len=64)
        for r, req in zip(out, reqs):
            ref = solo.generate([req])[0]
            assert r.tokens == ref.tokens

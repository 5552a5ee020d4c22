"""Speculative serving (ISSUE-5 acceptance paths).

The correctness bar is BIT-IDENTITY TO DENSE GREEDY DECODING for ANY
drafter: the target verifies every committed token, so acceptance rate
only moves throughput, never tokens. The discriminating cases are the
rollback edges — ring-cache wrap, freshly admitted slots, K past the
budget, repeated partial acceptance — where a lockstep or restore bug
would silently change tokens.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import DEFAULT_EXCLUDE, PruneConfig, greedy_prune
from repro.models import build_model
from repro.serve import (
    Request,
    ServeEngine,
    SpeculativeEngine,
    shallow_drafter,
)


@pytest.fixture(scope="module")
def lm():
    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=128,
                      num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
                      vocab_size=512, param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def artifact(lm):
    cfg, model, params = lm
    pcfg = PruneConfig(
        scheme="tile_pattern", exclude=tuple(DEFAULT_EXCLUDE),
        overrides={".*": {"tile_block_p": 64, "tile_group_q": 8,
                          "tile_keep": 4}},
    )
    return greedy_prune(params, pcfg).to_artifact(arch="tiny").pack()


@pytest.fixture(scope="module")
def swa_lm():
    cfg = ModelConfig(name="tinyw", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      param_dtype="float32", sliding_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


def _mixed_requests(cfg, n=5):
    return [Request(uid=i, prompt=(jnp.arange(3 + 4 * i) + i) % cfg.vocab_size,
                    max_new_tokens=4 + i) for i in range(n)]


def _caches_match(a, b, *, exact_kv: bool):
    """Geometry (pos/slot_pos) must be EXACT; k/v bytes are bit-exact on
    the non-ring path and float-epsilon on the ring two-part-attention
    path (different reduction order than sequential decode)."""
    for key in ("pos", "slot_pos"):
        if not jnp.array_equal(a[key], b[key]):
            return False
    for key in ("k", "v"):
        if exact_kv:
            if not jnp.array_equal(a[key], b[key]):
                return False
        elif not jnp.allclose(a[key], b[key], atol=1e-5):
            return False
    return True


# Layer 0's k/v project the same embeddings in the chunked and the
# sequential program, so they stay bit-equal. Deeper layers read attention
# outputs, and XLA:CPU (jax 0.9) contracts the K-query einsums of
# ``chunk_attention`` with its operands swapped relative to the one-query
# einsums of ``decode_attention``: the two agree to float32 rounding, no
# longer bit for bit (atol 1e-5, as on the ring path).
def _layer0_kv_equal(a, b):
    return all(jnp.array_equal(a[key][0], b[key][0]) for key in ("k", "v"))


# ---------------------------------------------------------------------------
# model-level primitives: verify_chunk + snapshot/rollback
# ---------------------------------------------------------------------------

class TestVerifyChunk:
    def test_chunk_logits_match_sequential_decode(self, lm):
        """verify_chunk's per-position logits and final cache equal K
        sequential decode_steps — the chunked-verify contract."""
        cfg, model, params = lm
        prompts = jnp.stack([jnp.arange(6) % 512, (jnp.arange(6) + 3) % 512])
        cache, _ = model.prefill(params, prompts, 32)
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 512, (2, 4)), jnp.int32)
        c_seq, seq = cache, []
        for i in range(4):
            c_seq, lg = model.decode_step(params, c_seq, toks[:, i:i + 1])
            seq.append(lg[:, 0])
        seq = jnp.stack(seq, 1)
        c_ch, ch = model.verify_chunk(params, cache, toks)
        assert jnp.allclose(seq, ch, atol=1e-5)
        assert jnp.array_equal(jnp.argmax(seq, -1), jnp.argmax(ch, -1))
        assert _caches_match(c_seq, c_ch, exact_kv=False)
        assert _layer0_kv_equal(c_seq, c_ch)

    def test_rollback_equals_partial_decode(self, lm):
        """Snapshot → verify K → rollback(keep) must leave a cache
        bit-identical to decoding ONLY the kept tokens (per-row keep)."""
        cfg, model, params = lm
        prompts = jnp.stack([jnp.arange(6) % 512, (jnp.arange(8) + 1)[:6]])
        cache, _ = model.prefill(params, prompts, 32)
        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, 512, (2, 5)), jnp.int32)
        snap = model.cache_snapshot(cache, 5)
        c_ch, _ = model.verify_chunk(params, cache, toks)
        keep = jnp.asarray([2, 5], jnp.int32)
        c_rb = model.cache_rollback(c_ch, snap, keep)
        assert list(np.asarray(c_rb["pos"])) == [6 + 2, 6 + 5]
        # row-wise reference: row 0 decodes 2 tokens, row 1 decodes 5 —
        # beyond row 0's keep only row 1's slices of the stepped cache
        # advance
        c_ref = cache
        for i in range(5):
            c_nxt, _ = model.decode_step(params, c_ref, toks[:, i:i + 1])
            if i < 2:
                c_ref = c_nxt
            else:
                c_ref = {
                    "k": c_ref["k"].at[:, 1].set(c_nxt["k"][:, 1]),
                    "v": c_ref["v"].at[:, 1].set(c_nxt["v"][:, 1]),
                    "slot_pos": c_ref["slot_pos"].at[1].set(
                        c_nxt["slot_pos"][1]),
                    "pos": c_ref["pos"].at[1].set(c_nxt["pos"][1]),
                }
        assert _caches_match(c_ref, c_rb, exact_kv=False)
        assert _layer0_kv_equal(c_ref, c_rb)

    def test_rollback_across_ring_wrap(self, swa_lm):
        """Ring cache (SWA): verify across the wrap boundary overwrites
        live window history; rollback must RESTORE it (masking alone
        cannot). Geometry exact, k/v to float epsilon."""
        cfg, model, params = swa_lm
        cache, _ = model.prefill(params, jnp.arange(12)[None, :] % 64, 32)
        assert cache["k"].shape[2] == 8          # ring capacity = window
        toks = jnp.asarray(
            np.random.default_rng(2).integers(0, 64, (1, 5)), jnp.int32)
        snap = model.cache_snapshot(cache, 5)
        c_ch, ch = model.verify_chunk(params, cache, toks)
        # chunk logits match sequential decode through the wrap
        c_seq, seq = cache, []
        for i in range(5):
            c_seq, lg = model.decode_step(params, c_seq, toks[:, i:i + 1])
            seq.append(lg[:, 0])
        assert jnp.allclose(jnp.stack(seq, 1), ch, atol=1e-4)
        # rollback to keep=2: equal to decoding only 2 tokens
        c_rb = model.cache_rollback(c_ch, snap, jnp.asarray([2], jnp.int32))
        c_ref = cache
        for i in range(2):
            c_ref, _ = model.decode_step(params, c_ref, toks[:, i:i + 1])
        assert _caches_match(c_ref, c_rb, exact_kv=False)

    def test_rollback_on_freshly_admitted_slot(self, lm):
        """Per-row geometry: a slot freshly admitted via prefill_into_slot
        (its own pos, its own slot_pos row) rolls back independently of a
        live batch-mate."""
        cfg, model, params = lm
        cache = model.init_cache(2, 32)
        cache, _ = model.prefill_into_slot(
            params, cache, jnp.arange(10)[None, :] % 512, 0)
        cache, _ = model.prefill_into_slot(
            params, cache, (jnp.arange(4) + 7)[None, :] % 512, 1)
        assert list(np.asarray(cache["pos"])) == [10, 4]
        toks = jnp.asarray(
            np.random.default_rng(3).integers(0, 512, (2, 3)), jnp.int32)
        snap = model.cache_snapshot(cache, 3)
        c_ch, _ = model.verify_chunk(params, cache, toks)
        c_rb = model.cache_rollback(
            c_ch, snap, jnp.asarray([0, 3], jnp.int32))
        assert list(np.asarray(c_rb["pos"])) == [10, 7]
        # row 0 rolled all the way back: bit-identical to pre-verify
        assert jnp.array_equal(c_rb["k"][:, 0], cache["k"][:, 0])
        assert jnp.array_equal(c_rb["slot_pos"][0], cache["slot_pos"][0])

    def test_verify_chunk_rejects_recurrent_families(self):
        cfg = ModelConfig(name="x", family="ssm", num_layers=2, d_model=32,
                          num_heads=4, num_kv_heads=4, head_dim=8, d_ff=64,
                          vocab_size=64, slstm_every=2,
                          param_dtype="float32")
        model = build_model(cfg)
        with pytest.raises(NotImplementedError, match="recurrent state"):
            model.verify_chunk(None, {"pos": jnp.zeros((1,))},
                               jnp.zeros((1, 2), jnp.int32))


# ---------------------------------------------------------------------------
# engine: bit-identity + lockstep
# ---------------------------------------------------------------------------

class TestSpeculativeIdentity:
    @pytest.mark.parametrize("packed_draft", [False, True])
    def test_mixed_length_bit_identical_to_dense(self, lm, artifact,
                                                 packed_draft):
        """THE acceptance bar: greedy speculative output == dense greedy
        for mixed-length batches, dense and packed drafter."""
        cfg, model, params = lm
        reqs = _mixed_requests(cfg)
        dense = ServeEngine(model, params, batch_size=4, max_seq_len=64)
        ref = [r.tokens for r in dense.generate(reqs)]
        draft = artifact if packed_draft else artifact.bind(model,
                                                            packed=False)
        spec = SpeculativeEngine(model, params, draft, batch_size=4,
                                 max_seq_len=64, draft_k=4)
        out = spec.generate(reqs)
        assert [r.tokens for r in out] == ref
        assert [r.uid for r in out] == [r.uid for r in reqs]

    def test_serve_engine_wiring(self, lm, artifact):
        """ServeEngine(speculative=..., draft_k=...) routes generate
        through the speculative engine and exposes its stats."""
        cfg, model, params = lm
        reqs = _mixed_requests(cfg, 3)
        dense = ServeEngine(model, params, batch_size=4, max_seq_len=64)
        eng = ServeEngine(model, params, batch_size=4, max_seq_len=64,
                          speculative=artifact, draft_k=4)
        assert [r.tokens for r in eng.generate(reqs)] == \
            [r.tokens for r in dense.generate(reqs)]
        assert eng.speculative.stats["rounds"] > 0
        assert 0.0 <= eng.speculative.stats["acceptance_rate"] <= 1.0

    def test_lockstep_under_repeated_partial_acceptance(self, lm):
        """A disagreeing drafter (truncated layers) forces rejection and
        rollback nearly every round; output must STILL be bit-identical
        to dense — the dual-cache lockstep guarantee — and both caches
        must sit at the same positions afterwards."""
        cfg, model, params = lm
        reqs = [Request(uid=i, prompt=(jnp.arange(4 + 3 * i)) % 512,
                        max_new_tokens=12) for i in range(3)]
        dense = ServeEngine(model, params, batch_size=4, max_seq_len=64)
        ref = [r.tokens for r in dense.generate(reqs)]
        d_model, d_params = shallow_drafter(model, params, 1)
        spec = SpeculativeEngine(model, params, d_params,
                                 draft_model=d_model, batch_size=4,
                                 max_seq_len=64, draft_k=3)
        assert [r.tokens for r in spec.generate(reqs)] == ref
        st = spec.stats
        assert st["accepted"] < st["drafted"]    # real rejections happened
        assert st["rounds"] > len(ref[0]) // 4   # many partial rounds

    def test_k_larger_than_remaining_budget(self, lm, artifact):
        """draft_k past a request's budget: overflow tokens are dropped,
        the result is exactly the dense result."""
        cfg, model, params = lm
        reqs = [Request(uid=0, prompt=jnp.arange(5) % 512,
                        max_new_tokens=3),
                Request(uid=1, prompt=jnp.arange(5) % 512,
                        max_new_tokens=1)]
        dense = ServeEngine(model, params, batch_size=2, max_seq_len=64)
        spec = SpeculativeEngine(model, params, artifact, batch_size=2,
                                 max_seq_len=64, draft_k=8)
        assert [r.tokens for r in spec.generate(reqs)] == \
            [r.tokens for r in dense.generate(reqs)]
        assert [len(r.tokens) for r in spec.generate(reqs)] == [3, 1]

    def test_sliding_window_ring_identity(self, swa_lm):
        """SWA ring cache: speculative == dense through cache wraparound,
        under full acceptance AND under constant rejection."""
        cfg, model, params = swa_lm
        reqs = [Request(uid=i, prompt=jnp.arange(3 + 5 * i) % 64,
                        max_new_tokens=10) for i in range(3)]
        dense = ServeEngine(model, params, batch_size=2, max_seq_len=32)
        ref = [r.tokens for r in dense.generate(reqs)]
        full = SpeculativeEngine(model, params, params, batch_size=2,
                                 max_seq_len=32, draft_k=4)
        assert [r.tokens for r in full.generate(reqs)] == ref
        d_model, d_params = shallow_drafter(model, params, 1)
        rej = SpeculativeEngine(model, params, d_params,
                                draft_model=d_model, batch_size=2,
                                max_seq_len=32, draft_k=4)
        assert [r.tokens for r in rej.generate(reqs)] == ref

    def test_eos_trim(self, lm, artifact):
        """eos_id trims speculative output post-hoc exactly like the
        chunked engine (eos emitted, nothing past it)."""
        cfg, model, params = lm
        base = Request(uid=0, prompt=jnp.arange(8) % 512, max_new_tokens=8)
        dense = ServeEngine(model, params, batch_size=2, max_seq_len=64)
        full = dense.generate([base])[0].tokens
        eos = full[3]
        req = Request(uid=0, prompt=jnp.arange(8) % 512, max_new_tokens=8,
                      eos_id=eos)
        spec = SpeculativeEngine(model, params, artifact, batch_size=2,
                                 max_seq_len=64, draft_k=4)
        assert spec.generate([req])[0].tokens == \
            dense.generate([req])[0].tokens

    def test_capacity_validation(self, lm, artifact):
        cfg, model, params = lm
        spec = SpeculativeEngine(model, params, artifact, batch_size=2,
                                 max_seq_len=16, draft_k=4)
        bad = Request(uid=0, prompt=jnp.arange(10) % 512, max_new_tokens=8)
        with pytest.raises(ValueError, match="exceeds target cache"):
            spec.generate([bad])


# ---------------------------------------------------------------------------
# stochastic speculative + per-request seeds
# ---------------------------------------------------------------------------

class TestStochasticSpeculative:
    def test_seeded_reproducible_across_engines(self, lm, artifact):
        """Request.seed pins the stream: two engines with different
        engine seeds emit the same tokens for the seeded request."""
        cfg, model, params = lm
        reqs = [Request(uid=0, prompt=jnp.arange(6) % 512, max_new_tokens=8,
                        temperature=0.8, seed=42)]
        a = SpeculativeEngine(model, params, artifact, batch_size=2,
                              max_seq_len=64, draft_k=4, seed=0)
        b = SpeculativeEngine(model, params, artifact, batch_size=2,
                              max_seq_len=64, draft_k=4, seed=123)
        ta = [r.tokens for r in a.generate(reqs)]
        assert ta == [r.tokens for r in b.generate(reqs)]
        assert all(0 <= t < cfg.vocab_size for t in ta[0])
        assert len(ta[0]) == 8

    def test_greedy_mate_unaffected_by_stochastic_row(self, lm, artifact):
        """temperature routes per slot: a greedy request in a stochastic
        speculative chunk still matches pure-dense greedy serving."""
        cfg, model, params = lm
        mixed = [Request(uid=0, prompt=jnp.arange(6) % 512,
                         max_new_tokens=8, temperature=0.9, seed=7),
                 Request(uid=1, prompt=jnp.arange(6) % 512,
                         max_new_tokens=8)]
        dense = ServeEngine(model, params, batch_size=2, max_seq_len=64)
        spec = SpeculativeEngine(model, params, artifact, batch_size=2,
                                 max_seq_len=64, draft_k=4)
        out = spec.generate(mixed)
        assert out[1].tokens == dense.generate([mixed[1]])[0].tokens


# ---------------------------------------------------------------------------
# shallow drafter construction
# ---------------------------------------------------------------------------

class TestShallowDrafter:
    def test_shares_embed_and_head(self, lm):
        cfg, model, params = lm
        d_model, d_params = shallow_drafter(model, params, 1)
        assert d_model.config.num_layers == 1
        assert d_params["embed"] is params["embed"]
        leaves = jax.tree.leaves(d_params["blocks"])
        full = jax.tree.leaves(params["blocks"])
        assert all(l.shape[0] == 1 for l in leaves)
        assert all(jnp.array_equal(l, f[:1])
                   for l, f in zip(leaves, full))

    def test_bounds(self, lm):
        cfg, model, params = lm
        with pytest.raises(ValueError):
            shallow_drafter(model, params, 0)
        with pytest.raises(ValueError):
            shallow_drafter(model, params, cfg.num_layers + 1)

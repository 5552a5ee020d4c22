"""Scan decode + pack-time dispatch geometry (ISSUE-2 acceptance paths).

The device-resident ``LM.decode_many`` scan must be token-identical to the
legacy step-by-step loop (dense AND packed, greedy), the fused-epilogue
small-M plans must match the step-by-step math, and a batch smaller than
the kernels' tile sizes (the decode fast path) must serve correctly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import DEFAULT_EXCLUDE, PruneConfig, greedy_prune
from repro.models import build_model
from repro.serve import Request, ServeEngine
from repro.serve.sampler import greedy_sample


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


def _step_by_step(model, params, prompts, seq_len, steps):
    """The legacy decode loop: prefill, then one decode_step per token."""
    cache, logits = jax.jit(
        lambda p, x: model.prefill(p, x, seq_len))(params, prompts)
    decode = jax.jit(model.decode_step)
    tok = greedy_sample(logits)
    out = [tok]
    for _ in range(steps - 1):
        cache, logits = decode(params, cache, tok)
        tok = greedy_sample(logits)
        out.append(tok)
    return np.asarray(jnp.concatenate(out, axis=1))


class TestScanDecode:
    @pytest.mark.parametrize("packed", [False, True])
    def test_scan_matches_step_by_step(self, lm, artifact, packed):
        """decode_many's scan emits EXACTLY the legacy loop's tokens."""
        cfg, model, params = lm
        p = artifact.bind(model, packed=packed)
        B, S, steps = 4, 8, 6
        prompts = jax.random.randint(jax.random.PRNGKey(3), (B, S),
                                     0, cfg.vocab_size)
        ref = _step_by_step(model, p, prompts, 32, steps)

        cache, logits = jax.jit(
            lambda pp, x: model.prefill(pp, x, 32))(p, prompts)
        tok = greedy_sample(logits)
        _, rest = jax.jit(model.decode_many, static_argnums=(3,))(
            p, cache, tok, steps - 1)
        got = np.asarray(jnp.concatenate([tok, rest], axis=1))
        assert np.array_equal(got, ref)

    def test_engine_generate_matches_step_by_step(self, lm, artifact):
        """The refactored engine end-to-end == the legacy loop's tokens."""
        cfg, model, params = lm
        eng = ServeEngine(model, artifact, batch_size=4, max_seq_len=32,
                          packed=True)
        B, S, steps = 4, 8, 6
        prompts = jax.random.randint(jax.random.PRNGKey(4), (B, S),
                                     0, cfg.vocab_size)
        ref = _step_by_step(model, eng.params, prompts, 32, steps)
        reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=steps)
                for i in range(B)]
        got = [r.tokens for r in eng.generate(reqs)]
        assert got == [list(map(int, ref[i])) for i in range(B)]

    def test_partial_chunk_empty_slots(self, lm, artifact):
        """A chunk smaller than batch_size pads with masked empty slots and
        still produces the same tokens as a full-batch run of the same
        requests."""
        cfg, model, params = lm
        eng = ServeEngine(model, artifact, batch_size=4, max_seq_len=32,
                          packed=True)
        reqs = [Request(uid=i, prompt=(jnp.arange(6) + i) % cfg.vocab_size,
                        max_new_tokens=4) for i in range(2)]   # n=2 < B=4
        out = eng.generate(reqs)
        assert [r.uid for r in out] == [0, 1]
        assert all(len(r.tokens) == 4 for r in out)
        # per-chunk trim: a 1-request chunk decodes its own max_new only
        solo = eng.generate([reqs[0]])
        assert solo[0].tokens == out[0].tokens

    def test_small_batch_packed_decode(self, lm, artifact):
        """batch=2 (M=2, far below every kernel tile) — the small-M decode
        fast path — stays token-identical to dense serving."""
        cfg, model, params = lm
        dense = ServeEngine(model, artifact, batch_size=2, max_seq_len=32,
                            packed=False)
        packed = ServeEngine(model, artifact, batch_size=2, max_seq_len=32,
                             packed=True)
        reqs = [Request(uid=i, prompt=jnp.arange(6 + i) % cfg.vocab_size,
                        max_new_tokens=5) for i in range(2)]
        td = [r.tokens for r in dense.generate(reqs)]
        tp = [r.tokens for r in packed.generate(reqs)]
        assert td == tp


def _tiny(**kw):
    base = dict(name="tiny", family="dense", num_layers=3, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                vocab_size=256, param_dtype="float32")
    return ModelConfig(**{**base, **kw})


CARRIED = {
    # window 8 below the 24-position cache: a ring of capacity 8, and a
    # 6-token prompt decoded 14 steps wraps it past capacity
    "ring": dict(sliding_window=8),
    # attention + mamba heads: the mamba state rides the scan beside the
    # carried K/V stack
    "hybrid": dict(family="hybrid", mamba_heads=4, mamba_head_dim=16,
                   ssm_state=8),
}


class TestCarriedCache:
    """The layer scan carries the stacked K/V cache and writes one row per
    layer; the scan over steps and the step-by-step loop must agree token
    for token, and leave the same cache behind."""

    @pytest.mark.parametrize("name", sorted(CARRIED))
    def test_carried_cache_matches_step_by_step(self, name):
        cfg = _tiny(**CARRIED[name])
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(5))
        B, S, steps, seq_len = 3, 6, 14, 24
        prompts = jax.random.randint(jax.random.PRNGKey(6), (B, S),
                                     0, cfg.vocab_size)
        ref = _step_by_step(model, params, prompts, seq_len, steps)

        cache, logits = jax.jit(
            lambda pp, x: model.prefill(pp, x, seq_len))(params, prompts)
        if name == "ring":
            assert cache["k"].shape[2] == 8 < S + steps
        tok = greedy_sample(logits)
        cache, rest = jax.jit(model.decode_many, static_argnums=(3,))(
            params, cache, tok, steps - 1)
        got = np.asarray(jnp.concatenate([tok, rest], axis=1))
        assert np.array_equal(got, ref)

        # the loop's cache after the same steps, byte for byte; its logits
        # against a forward over the whole sequence, which keeps no cache
        loop = jax.jit(lambda pp, x: model.prefill(pp, x, seq_len))(
            params, prompts)[0]
        decode = jax.jit(model.decode_step)
        seq = jnp.concatenate([prompts, jnp.asarray(ref)], axis=1)
        h, _, _ = model.hidden_states(params, seq[:, :-1])
        full = model.lm_logits(params, h)
        for t in range(steps - 1):
            loop, logits = decode(params, loop, seq[:, S + t:S + t + 1])
            err = float(jnp.max(jnp.abs(logits[:, 0] - full[:, S + t])))
            assert err < 2e-3, (t, err)
        for key in ("k", "v", "slot_pos", "pos"):
            assert np.array_equal(np.asarray(cache[key]),
                                  np.asarray(loop[key])), key
        if name == "hybrid":
            for a, b in zip(jax.tree.leaves(cache["mamba"]),
                            jax.tree.leaves(loop["mamba"])):
                assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_admission_mid_decode(self, lm):
        """A prompt admitted into one slot between two decode scans leaves
        every other slot's K/V rows byte-identical, and the scans emit the
        step-by-step loop's tokens in every slot, before and after it."""
        cfg, model, params = lm
        B, seq_len, first, second = 4, 32, 5, 6
        keys = jax.random.split(jax.random.PRNGKey(7), B + 1)
        prompts = [jax.random.randint(k, (1, 4 + 2 * i), 0, cfg.vocab_size)
                   for i, k in enumerate(keys[:B])]
        late = jax.random.randint(keys[B], (1, 9), 0, cfg.vocab_size)
        admit = jax.jit(model.prefill_into_slot)
        scan = jax.jit(model.decode_many, static_argnums=(3,))
        step = jax.jit(model.decode_step)

        def admitted(cache, tok, prompt, slot):
            cache, logits = admit(params, cache, prompt, jnp.int32(slot))
            return cache, tok.at[slot].set(greedy_sample(logits)[0])

        def fill():
            cache = model.init_cache(B, seq_len)
            tok = jnp.zeros((B, 1), jnp.int32)
            for slot, p in enumerate(prompts):
                cache, tok = admitted(cache, tok, p, slot)
            return cache, tok

        def loop(cache, tok, n):
            out = []
            for _ in range(n):
                cache, logits = step(params, cache, tok)
                tok = greedy_sample(logits)
                out.append(tok)
            return cache, tok, np.asarray(jnp.concatenate(out, axis=1))

        cache, tok = fill()
        cache, got1 = scan(params, cache, tok, first)
        tok = jnp.asarray(got1[:, -1:])
        before = jax.tree.map(np.asarray, cache)
        cache, tok = admitted(cache, tok, late, 2)
        others = [b for b in range(B) if b != 2]
        for key in ("k", "v"):
            assert np.array_equal(np.asarray(cache[key])[:, others],
                                  before[key][:, others]), key
        assert np.array_equal(np.asarray(cache["slot_pos"])[others],
                              before["slot_pos"][others])
        cache, got2 = scan(params, cache, tok, second)

        ref_cache, ref_tok = fill()
        ref_cache, ref_tok, ref1 = loop(ref_cache, ref_tok, first)
        ref_cache, ref_tok = admitted(ref_cache, ref_tok, late, 2)
        _, _, ref2 = loop(ref_cache, ref_tok, second)
        assert np.array_equal(np.asarray(got1), ref1)
        assert np.array_equal(np.asarray(got2), ref2)

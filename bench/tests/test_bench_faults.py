"""The check fails where the timed path is broken underneath, and its
control (the reference in float8) reads wider gaps than the program.

Faults a serving cell can have: a token altered where it is produced (the
sampler), and a decode step that returns its state unchanged (no KV row
written into the cache)."""

import pytest

from bench import control
from bench.tests import small

CELLS = ["qwen2-1.5b.long_prompt", "qwen2-1.5b.chat_batch"]


def _altered_sampler(logits):
    import jax.numpy as jnp

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(tok % 5 == 0, (tok + 1) % logits.shape[-1], tok)


def _cache_left_unchanged(kc, vc, slot_pos, k, v, pos, *, ring=False):
    return kc, vc, slot_pos


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_fails(cell, monkeypatch):
    import repro.serve.engine as engine

    monkeypatch.setattr(engine, "greedy_sample", _altered_sampler)
    line = small.run(small.small_ctx(cell))
    assert line["correct"] is False
    c = line["checks"]["widest_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_unchanged_cache_fails(cell, monkeypatch):
    import repro.models.transformer as transformer

    monkeypatch.setattr(transformer, "cache_insert", _cache_left_unchanged)
    line = small.run(small.small_ctx(cell))
    assert line["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(cell):
    """The control's tokens, put through the harness's own check in the
    program's place, come out not correct; the program's come out
    correct."""
    ctx = small.small_ctx(cell)
    r = control.readings(ctx)
    assert r["tokens"] > 0
    assert r["widest_gap"] <= ctx.workload["check"]["widest_gap"]
    assert r["control_gap"] > ctx.workload["check"]["widest_gap"]
    assert r["program_correct"] is True
    assert r["control_correct"] is False

"""The Pallas kernels compile for a described TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the TPU's kernel
compiler refuses: a block not tiled by (8, 128), a gather it cannot lower,
more VMEM than a kernel may use. These tests compile each kernel of the
main path for one chip of a described ``v5e:2x2`` topology — nothing runs —
and check that the compiled HLO holds the kernel as a ``tpu_custom_call``.

Shapes are Qwen2-1.5B's (d_model 1536, 12 query / 2 KV heads of 128, d_ff
8960, vocabulary 151,936, bf16) at a 512-row prefill, and one VGG-16 layer
for the conv. The engine's programs compile whole at that width, with the
vocabulary cut; the decode chunk's compiled HLO is checked for how it
moves the KV cache.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.column_gemm import column_gemm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pattern_conv import pattern_conv
from repro.kernels.pattern_gemm import pattern_gemm

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 1536, 8960, 12, 2, 128
VOCAB = 151_936
PREFILL_M = 512
KEEP, GROUP, BLOCK_P = 4, 8, 128      # tile_pattern 4-of-8, 128-wide panels
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_hlo(fn, *args) -> str:
    """HLO text of ``fn`` compiled for the described chip, kernels forced on."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(hlo: str, name: str):
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls, "no tpu_custom_call in the compiled HLO"
    assert any(name in ln for ln in calls), \
        f"no {name} kernel among {len(calls)} tpu_custom_calls"


@pytest.mark.parametrize("q,p", [
    (D_MODEL, HEADS * HEAD_DIM),          # wq
    (D_MODEL, KV_HEADS * HEAD_DIM),       # wk / wv
    (D_MODEL, D_FF),                      # w_up / w_gate
    (D_FF, D_MODEL),                      # w_down
    (D_MODEL, VOCAB),                     # lm_head: lane table > SMEM
], ids=["q_proj", "kv_proj", "up_proj", "down_proj", "lm_head"])
def test_pattern_gemm(one_chip, q, p):
    kp, nb = q * KEEP // GROUP, p // BLOCK_P
    hlo = _compile_hlo(
        lambda x, w, li: pattern_gemm(x, w, li, block_m=128, interpret=False),
        _spec((PREFILL_M, q), BF16, one_chip),
        _spec((nb, kp, BLOCK_P), BF16, one_chip),
        _spec((nb, kp), jnp.int32, one_chip))
    _assert_kernel(hlo, "pattern_gemm")


def test_column_gemm(one_chip):
    k = D_FF // 2                           # half of the columns kept
    hlo = _compile_hlo(
        lambda x, w, kept: column_gemm(x, w, kept, interpret=False),
        _spec((PREFILL_M, D_FF), BF16, one_chip),
        _spec((k, D_MODEL), BF16, one_chip),
        _spec((k,), jnp.int32, one_chip))
    _assert_kernel(hlo, "column_gemm")


def test_pattern_conv_vgg16_conv3(one_chip):
    # VGG-16 conv3_2 at batch 1: 56×56, 256 → 256 channels, 4 of 9 taps
    c = a = 256
    taps = np.tile(np.array([1, 3, 4, 5], np.int32), (c, 1))
    hlo = _compile_hlo(
        lambda x, w: pattern_conv(x, w, taps, interpret=False,
                                  activation="relu"),
        _spec((1, 56, 56, c), BF16, one_chip),
        _spec((4 * c, a), BF16, one_chip))
    _assert_kernel(hlo, "pattern_conv_gemm")


@pytest.mark.parametrize("seq", [512, 2048])
def test_flash_attention(one_chip, seq):
    hlo = _compile_hlo(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        _spec((1, seq, HEADS, HEAD_DIM), BF16, one_chip),
        _spec((1, seq, KV_HEADS, HEAD_DIM), BF16, one_chip),
        _spec((1, seq, KV_HEADS, HEAD_DIM), BF16, one_chip))
    _assert_kernel(hlo, "flash_attention")


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_continuous_engine_programs(one_chip, monkeypatch, packed):
    """Slot admission (tiled and ragged prompts) and the decode chunk of
    ``ContinuousEngine`` at Qwen2-1.5B width, one layer, a cut vocabulary.
    """
    import dataclasses

    import repro.kernels.ops as kops
    import repro.sparse.registry as reg
    from repro.configs import get_config
    from repro.core import greedy_prune
    from repro.launch.prune import prune_config_for
    from repro.models import build_model
    from repro.serve.engine import ContinuousEngine

    # JAX sees the CPU here: take the compiled-kernel branch the chip takes
    monkeypatch.setattr(kops, "_default_interpret", lambda: False)
    monkeypatch.setattr(reg, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), num_layers=1,
                              vocab_size=4096)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    art = greedy_prune(params, prune_config_for(
        scheme="tile_pattern", rate=2, iters=1)).to_artifact().pack()
    batch, max_seq = 4, 544
    eng = ContinuousEngine(model, art, batch_size=batch, max_seq_len=max_seq,
                           packed=packed)
    assert not eng.bind_report["fallbacks"]
    spec = lambda a: _spec(a.shape, a.dtype, one_chip)
    p = jax.tree.map(spec, eng.params)
    cache = jax.tree.map(spec, jax.eval_shape(
        lambda: model.init_cache(batch, max_seq)))
    tok = _spec((batch, 1), jnp.int32, one_chip)
    slot = _spec((), jnp.int32, one_chip)
    for S, kernels in ((512, ("flash_attention",)), (203, ())):
        hlo = eng._admit_greedy.lower(
            p, cache, tok, _spec((1, S), jnp.int32, one_chip),
            slot).compile().as_text()
        for name in kernels + (("pattern_gemm",) if packed else ()):
            _assert_kernel(hlo, name)
    eng._chunk_greedy.lower(p, cache, tok, _spec((batch,), jnp.int32,
                                                 one_chip), 8).compile()


def _stacked_cache_ops(hlo: str, row_shape) -> list:
    """``(opcode, line)`` of each op whose output holds more than one
    layer of a K/V cache whose per-layer rows are ``row_shape``."""
    rows = ",".join(map(str, row_shape))
    pat = re.compile(r"%\S+ = bf16\[([\d,]+)," + rows
                     + r"\]\{[^}]*\} ([\w-]+)\(")
    out = []
    for ln in hlo.splitlines():
        m = pat.search(ln)
        if m and math.prod(int(d) for d in m.group(1).split(",")) > 1:
            out.append((m.group(2), ln.strip()))
    return out


def test_decode_chunk_updates_the_cache_in_place(one_chip, monkeypatch):
    """``ContinuousEngine``'s decode chunk (8 steps) at Qwen2-1.5B width,
    8 layers so the 4-way unrolled layer scan loops, 32 slots, dense
    weights: the stacked K/V cache rides the layer scan's carry. No op
    outputs a buffer of more than one layer of the cache except the
    in-place update of the carried stack, and the temporaries that grow
    with the cache stay below one layer's K and V. (The scan's slices of
    the weights do not depend on the cache: a second compile at a short
    cache takes them out of the comparison.)"""
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.engine import ContinuousEngine

    cfg = dataclasses.replace(get_config("qwen2-1.5b"), num_layers=8,
                              vocab_size=4096)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = 32
    spec = lambda a: _spec(a.shape, a.dtype, one_chip)

    def chunk(max_seq):
        # the engine donates the cache to its chunk program on a TPU only
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            eng = ContinuousEngine(model, params, batch_size=batch,
                                   max_seq_len=max_seq, packed=False)
        cache = jax.tree.map(spec, jax.eval_shape(
            lambda: model.init_cache(batch, max_seq)))
        compiled = eng._chunk_greedy.lower(
            jax.tree.map(spec, params), cache,
            _spec((batch, 1), jnp.int32, one_chip),
            _spec((batch,), jnp.int32, one_chip), 8).compile()
        return compiled, cache["k"]

    compiled, k = chunk(1536)
    hlo = compiled.as_text()
    ops = _stacked_cache_ops(hlo, k.shape[1:])
    in_place = [ln for op, ln in ops
                if op == "fusion" and "aliasing_operands" in ln]
    assert len(in_place) >= 2, "no in-place update of the K and V stacks"
    moved = [ln for op, ln in ops
             if op not in ("parameter", "get-tuple-element", "bitcast",
                           "scatter", "fusion")
             or (op == "fusion" and "aliasing_operands" not in ln)]
    assert not moved, "\n".join(ln[:200] for ln in moved[:8])

    slab = 2 * math.prod(k.shape[1:]) * k.dtype.itemsize
    short, _ = chunk(128)
    grown = (compiled.memory_analysis().temp_size_in_bytes
             - short.memory_analysis().temp_size_in_bytes)
    assert grown < slab, (grown, slab)

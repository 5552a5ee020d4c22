"""Required operations and bytes, against hand counts at a small size, and
each configuration's architecture module against the program's model."""

import dataclasses
import os

import pytest

from bench import peaks, spec, work

QWEN2 = spec.load_arch("qwen2")
CONFIGS = sorted(f[:-len(".json")] for f in
                 os.listdir(os.path.join(spec.BENCH, "configs")))


def small_shapes(**kw):
    base = dict(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16,
                d_ff=128, vocab=512, qkv_bias=True, rope_theta=1e6, eps=1e-5,
                group=8, keep=4, block=32,
                packed=("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                        "lm_head"))
    base.update(kw)
    return QWEN2.Shapes(**base)


def test_packed_gemm_hand_count():
    w = work.packed_gemm(M=16, K=64, O=96, keep=4, group=8, block=32)
    kp = 32                                       # 64 lanes keep 4 of 8
    assert w.flops == 2 * 16 * kp * 96
    assert w.bytes == 2 * kp * 96 + 4 * 3 * kp + 2 * (16 * 64 + 16 * 96)
    wb = work.packed_gemm(M=16, K=64, O=96, keep=4, group=8, block=32,
                          bias=True)
    assert wb.bytes == w.bytes + 2 * 96


def test_flash_prefill_hand_count():
    w = work.flash_prefill(S=4, heads=2, kv_heads=1, head_dim=8)
    # 10 causal query-key pairs, scores and values, 2 flops each
    assert w.flops == 2 * 2 * 8 * 2 * 10
    assert w.bytes == 2 * (2 * 4 * 2 * 8 + 2 * 4 * 1 * 8)


def test_prefill_and_decode_at_reduced_size():
    s = small_shapes()
    S = 32
    gemm_flops = 2 * S * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128) // 2
    attn = 4 * 16 * 4 * S * (S + 1) / 2
    head = 2 * 1 * 64 * 512 // 2
    assert QWEN2.prefill(s, S).flops == 2 * (gemm_flops + attn) + head
    tok = QWEN2.decode_token_flops(s, 40)
    kept = (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128) // 2
    assert tok == 2 * (2 * kept + 4 * 16 * 4 * 40) + 2 * 64 * 512 // 2
    step = QWEN2.decode_step(s, [40, 10])
    assert step.flops == QWEN2.decode_token_flops(s, 40) + \
        QWEN2.decode_token_flops(s, 10)
    kv = 2 * 2 * 2 * 2 * 16            # bytes of K and V per position
    assert step.bytes == QWEN2.weight_bytes(s) + kv * 50 + 2 * 64 * 2


def test_dense_head_counts_in_full():
    s = small_shapes()
    d = dataclasses.replace(s, packed=s.packed[:-1])
    assert QWEN2.gemm(d, "lm_head", 3).flops == 2 * 3 * 64 * 512
    assert QWEN2.weight_bytes(d) - QWEN2.weight_bytes(s) == pytest.approx(
        2 * 64 * 512 - (2 * 32 * 512 + 4 * (512 // 32) * 32))


def test_roofline_seconds_and_bound():
    p = peaks.peaks_of("TPU v5 lite")
    w = work.Work(flops=197e12, bytes=819e9 / 2)
    assert w.seconds(p) == pytest.approx(1.0) and w.bound(p) == "compute"
    m = work.Work(flops=1.0, bytes=819e9)
    assert m.seconds(p) == pytest.approx(1.0) and m.bound(p) == "memory"


def test_unknown_device_raises():
    with pytest.raises(KeyError):
        peaks.peaks_of("TPU v99")


# Fields of an architecture's ``Shapes`` and of the program's ``ModelConfig``
# that state the same size; the first five every architecture has.
SAME = {"layers": "num_layers", "vocab": "vocab_size", "heads": "num_heads",
        "kv_heads": "num_kv_heads", "head_dim": "head_dim",
        "d_model": "d_model", "d_ff": "d_ff", "eps": "norm_eps",
        "tied": "tie_embeddings"}


@pytest.mark.parametrize("name", CONFIGS)
def test_config_files_match_the_program_config(name):
    cfg = spec.load_config(name)
    arch = spec.load_arch(cfg["arch"])
    mc = arch.model_config(cfg)
    s = arch.Shapes.of(cfg)
    common = [k for k in SAME if hasattr(s, k)]
    assert common[:5] == list(SAME)[:5]
    assert [getattr(mc, SAME[k]) for k in common] == [
        getattr(s, k) for k in common]


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("name", CONFIGS)
def test_weights_have_the_program_tree(name, tied):
    """The seed's weights, at the small cut of the configuration's
    architecture, are the tree the program's model builds, with an
    ``lm_head`` leaf only where the head is not tied."""
    import functools

    import jax

    from bench import weights
    from repro.models import build_model

    cfg = spec.load_config(name)
    arch = spec.load_arch(cfg["arch"])
    cfg = dict(arch.small_config(cfg), tie_word_embeddings=tied)
    s = arch.Shapes.of(cfg)
    model = build_model(arch.model_config(cfg))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(functools.partial(arch._make, s),
                         weights.key_of(3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [x.shape for x in jax.tree.leaves(got)] == [
        x.shape for x in jax.tree.leaves(want)]
    assert ("lm_head" in got) is not tied

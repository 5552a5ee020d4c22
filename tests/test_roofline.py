"""Validation of the trip-count-aware HLO cost parser (roofline inputs).

The contract (hlo_costs docstring): agreement with XLA ``cost_analysis`` on
unrolled graphs; exactly ×trip_count on scanned graphs (where XLA counts the
loop body once); slice-accurate byte costing for the scan-over-layers weight
access pattern.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analyze_hlo, chip_peaks, roofline_terms
from repro.roofline.hw import HBM_BW, ICI_BW, PEAK_FLOPS_BF16


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def _xla_costs(compiled):
    return compiled.cost_analysis()


class TestUnrolled:
    def test_matmul_chain_matches_xla(self):
        def f(x, ws):
            for w in ws:
                x = jnp.tanh(x @ w)
            return x

        x = jnp.zeros((256, 512), jnp.float32)
        ws = [jnp.zeros((512, 512), jnp.float32) for _ in range(4)]
        c = _compile(f, x, ws)
        mine = analyze_hlo(c.as_text())
        xla = _xla_costs(c)
        assert mine.flops == pytest.approx(xla["flops"], rel=0.02)
        assert mine.bytes == pytest.approx(xla["bytes accessed"], rel=0.10)

    def test_conv_flops(self):
        def f(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "OIHW", "NHWC"))

        x = jnp.zeros((2, 16, 16, 8), jnp.float32)
        w = jnp.zeros((16, 8, 3, 3), jnp.float32)
        c = _compile(f, x, w)
        mine = analyze_hlo(c.as_text())
        # 2 * out_elems * (in_ch*kh*kw)
        expect = 2.0 * (2 * 16 * 16 * 16) * (8 * 3 * 3)
        assert mine.flops == pytest.approx(expect, rel=0.02)


class TestScanned:
    def test_scan_flops_scaled_by_trip_count(self):
        L = 12

        def g(x, ws):
            def body(x, w):
                return jnp.tanh(x @ w), None

            x, _ = jax.lax.scan(body, x, ws)
            return x

        x = jnp.zeros((256, 512), jnp.float32)
        ws = jnp.zeros((L, 512, 512), jnp.float32)
        c = _compile(g, x, ws)
        mine = analyze_hlo(c.as_text())
        expect = 2.0 * 256 * 512 * 512 * L
        assert mine.flops == pytest.approx(expect, rel=0.02)
        # XLA counts the body once — parser must be ~L/1 of it
        assert mine.flops > 0.8 * L * _xla_costs(c)["flops"] / 1.4

    def test_scan_bytes_slice_accurate(self):
        """Stacked-weight dynamic-slice must cost the SLICE, not the stack.

        Over-counting would show bytes ≳ L × stack_size; the true traffic is
        ~L × slice_size (each layer's weights read once per step)."""
        L = 16

        def g(x, ws):
            def body(x, w):
                return jnp.tanh(x @ w), None

            x, _ = jax.lax.scan(body, x, ws)
            return x

        x = jnp.zeros((128, 256), jnp.float32)
        ws = jnp.zeros((L, 256, 256), jnp.float32)
        c = _compile(g, x, ws)
        mine = analyze_hlo(c.as_text())
        stack_bytes = L * 256 * 256 * 4
        slice_bytes = 256 * 256 * 4
        act_bytes = 128 * 256 * 4
        # generous ceiling: a few× (slice + activations) per iteration —
        # NOT quadratic in L
        ceiling = L * 6 * (slice_bytes + act_bytes)
        assert mine.bytes < ceiling, (mine.bytes, ceiling)
        # floor: at least one slice read per iteration
        assert mine.bytes > L * slice_bytes


class TestCollectives:
    def test_psum_bytes_counted(self):
        mesh = jax.make_mesh((1,), ("d",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        import numpy as np

        @jax.jit
        def f(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P())
            ).sum()

        # single-device programs have no collectives; just assert the parser
        # returns a well-formed Costs with zero collective bytes
        x = jnp.zeros((128, 128), jnp.float32)
        c = jax.jit(lambda x: (x @ x).sum()).lower(x).compile()
        mine = analyze_hlo(c.as_text())
        assert mine.collective_total == 0.0


class TestChipPeaks:
    def test_v5e_published_peaks(self):
        p = chip_peaks("TPU v5 lite")
        assert (p.bf16_flops, p.hbm_bw) == (197e12, 819e9)
        assert "TPU v5e" in p.source
        assert PEAK_FLOPS_BF16 == p.bf16_flops and HBM_BW == p.hbm_bw

    def test_unknown_device_raises(self):
        with pytest.raises(KeyError, match="no published peaks"):
            chip_peaks("cpu")


class TestRooflineTerms:
    def test_terms_and_dominant(self):
        t = roofline_terms(1e15, 1e12, 1e10)
        assert t.compute_s == pytest.approx(1e15 / PEAK_FLOPS_BF16)
        assert t.memory_s == pytest.approx(1e12 / HBM_BW)
        assert t.collective_s == pytest.approx(1e10 / ICI_BW)
        assert t.dominant == "compute"
        assert t.step_s == t.compute_s

    def test_memory_bound_case(self):
        t = roofline_terms(1e12, 1e13, 1e8)
        assert t.dominant == "memory"

"""hlo_costs against ACTUAL lowered Pallas kernel HLO (not toy graphs).

test_roofline.py validates the parser on hand-built jnp graphs; these
tests lower the real sparse kernels (interpret mode — the kernel body
becomes traced jax ops, so the compiled HLO is the genuine grid/loop
structure) and pin two contracts:

  * the parser's flop count equals the analytic packed-GEMM model
    (2·M·Kp·P for pattern lanes, 2·M·K_kept·P for kept columns) — the
    same model ``roofline/attribution.py`` joins against measured walls;
  * the parser counts grid/loop trips that XLA's ``cost_analysis``
    attributes only once, so it never undercounts the kernel.

Also exercises the public helper API (``entry_name``/``while_parts``/
``trip_multipliers``/``rank_hlo_hotspots``) promoted out of the private
``hlo_costs`` internals for ``experiments/perf/diagnose.py``.
"""

import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core.projections import project_column, project_tile_pattern
from repro.kernels import ops
from repro.roofline import (
    analyze_hlo,
    entry_name,
    parse_hlo,
    rank_hlo_hotspots,
    shape_bytes,
    trip_multipliers,
    while_parts,
)

M, Q, P = 128, 256, 256


def _xla_costs(compiled):
    return compiled.cost_analysis()


def _lower(f, *args):
    return jax.jit(f).lower(*args).compile()


@pytest.fixture(scope="module")
def pattern_compiled():
    w = jax.random.normal(jax.random.PRNGKey(0), (Q, P), jnp.float32)
    wp = project_tile_pattern(w.T, block_p=128, group_q=8, keep=4).T
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        w_packed, lane_idx = ops.pack_tile_pattern(wp)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, Q), jnp.float32)
    return _lower(
        lambda x, wq, li: ops.tile_pattern_matmul(x, wq, li,
                                                  interpret=True),
        x, w_packed, lane_idx), w_packed


@pytest.fixture(scope="module")
def column_compiled():
    w = jax.random.normal(jax.random.PRNGKey(0), (Q, P), jnp.float32)
    wc = project_column(w.T, alpha=0.5).T
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        w_packed, kept = ops.pack_columns(wc)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, Q), jnp.float32)
    return _lower(
        lambda x, wq, ki: ops.column_matmul(x, wq, ki, interpret=True),
        x, w_packed, kept), w_packed


class TestPatternKernelCosts:
    def test_flops_match_packed_gemm_model(self, pattern_compiled):
        compiled, w_packed = pattern_compiled
        mine = analyze_hlo(compiled.as_text())
        # 4-of-8 lanes: every stored element multiplies once per row
        expect = 2.0 * M * w_packed.shape[0] * P
        assert mine.flops == pytest.approx(expect, rel=0.02)

    def test_counts_grid_trips_xla_misses(self, pattern_compiled):
        compiled, _ = pattern_compiled
        mine = analyze_hlo(compiled.as_text())
        xla = _xla_costs(compiled)
        # XLA costs a loop body once; the parser multiplies through, so
        # it must never come in below XLA's count
        assert mine.flops >= 0.95 * xla["flops"]
        assert mine.bytes > 0

    def test_bytes_cover_operands(self, pattern_compiled):
        compiled, w_packed = pattern_compiled
        mine = analyze_hlo(compiled.as_text())
        operand_bytes = (M * Q + w_packed.size + M * P) * 4
        assert mine.bytes >= operand_bytes


class TestColumnKernelCosts:
    def test_flops_match_packed_gemm_model(self, column_compiled):
        compiled, w_packed = column_compiled
        mine = analyze_hlo(compiled.as_text())
        expect = 2.0 * M * w_packed.shape[0] * P
        assert mine.flops == pytest.approx(expect, rel=0.02)

    def test_counts_grid_trips_xla_misses(self, column_compiled):
        compiled, _ = column_compiled
        mine = analyze_hlo(compiled.as_text())
        xla = _xla_costs(compiled)
        assert mine.flops >= 0.95 * xla["flops"]


class TestPublicHelpers:
    """The API diagnose.py migrated onto (was private _BODY/_COND/…)."""

    def test_shape_bytes(self):
        assert shape_bytes("f32[128,256]") == 128 * 256 * 4
        assert shape_bytes("bf16[8,16]") == 8 * 16 * 2

    def test_entry_and_trip_multipliers_on_scan(self):
        L = 6

        def g(x, ws):
            def body(x, w):
                return jnp.tanh(x @ w), None

            return jax.lax.scan(body, x, ws)[0]

        x = jnp.zeros((64, 128), jnp.float32)
        ws = jnp.zeros((L, 128, 128), jnp.float32)
        text = _lower(g, x, ws).as_text()
        comps = parse_hlo(text)
        ename = entry_name(text)
        assert ename in comps
        mult = trip_multipliers(comps, ename)
        assert mult[ename] == 1.0
        # the scan body computation is reached via a while op and
        # carries the trip count
        whiles = [ins for ins in comps[ename].instrs
                  if ins.opcode == "while"]
        assert whiles, "scan did not lower to a while op"
        body, cond = while_parts(whiles[0])
        assert body is not None and cond is not None
        assert mult.get(body) == pytest.approx(L)

    def test_rank_hlo_hotspots_on_kernel(self, pattern_compiled):
        compiled, _ = pattern_compiled
        spots = rank_hlo_hotspots(compiled.as_text(), top=5)
        assert spots["instruction_bytes_total"] > 0
        assert len(spots["memory_ops"]) <= 5
        assert all(r["bytes_x_trips"] > 0 for r in spots["memory_ops"])
        # single-device kernel: no collectives
        assert spots["collectives"] == []

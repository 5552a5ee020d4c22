"""Logical-axis sharding rules (shape-aware degradation, param mapping)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import build_model
from repro.parallel.sharding import (
    AxisRules,
    axis_rules,
    constrain,
    default_rules,
)


@pytest.fixture(scope="module")
def mesh():
    # single real device: a 1×1 mesh — rule LOGIC is device-count agnostic
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _rules(mesh_shape=(16, 16)):
    """Rules over a fake mesh-shape for spec logic tests (no devices)."""

    class FakeMesh:
        axis_names = ("data", "model")
        shape = dict(zip(("data", "model"), mesh_shape))

    r = default_rules.__wrapped__ if hasattr(default_rules, "__wrapped__") \
        else default_rules
    rules = AxisRules(
        rules=(("batch", ("data",)), ("heads", "model"), ("kv_heads", "model"),
               ("kv_dim", "model"), ("mlp", "model"), ("vocab", "model"),
               ("embed", "data")),
        mesh=FakeMesh(),
    )
    return rules


class TestSpecLogic:
    def test_basic(self):
        r = _rules()
        assert r.spec(("batch", None, "mlp")) == P("data", None, "model")

    def test_duplicate_axis_degrades(self):
        r = _rules()
        # both heads and mlp map to model → second one replicates
        assert r.spec(("heads", "mlp")) == P("model", None)

    def test_shape_aware_nondivisible(self):
        r = _rules()
        # batch=1 (long_500k) can't shard over data=16
        assert r.spec(("batch", None), shape=(1, 7)) == P(None, None)
        # granite vocab 49155 % 16 != 0 → replicated
        assert r.spec(("vocab", "embed"), shape=(49155, 2048)) == \
            P(None, "data")

    def test_kv_dim_fallback(self):
        r = _rules()
        # qwen2: kv_heads=2 < 16 → head_dim (=128) takes the model axis
        spec = r.spec(("layers", "batch", None, "kv_heads", "kv_dim"),
                      shape=(28, 128, 32768, 2, 128))
        assert spec == P(None, "data", None, None, "model")


class TestConstrain:
    def test_noop_without_rules(self):
        x = jnp.ones((4, 4))
        y = constrain(x, ("batch", None))
        assert (x == y).all()

    def test_applies_with_rules(self, mesh):
        rules = default_rules(mesh)
        with axis_rules(rules):
            y = jax.jit(lambda x: constrain(x, ("batch", None)))(
                jnp.ones((4, 4)))
        assert (y == 1).all()


class TestParamAxes:
    @pytest.mark.parametrize("arch", ["qwen2-1.5b", "deepseek-moe-16b",
                                      "xlstm-1.3b", "hymba-1.5b"])
    def test_logical_axes_congruent_with_params(self, arch):
        cfg = get_config(arch)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        axes = model.param_logical_axes()
        jax.tree.map(
            lambda s, a: None if len(a) == len(s.shape) else
            pytest.fail(f"rank mismatch {a} vs {s.shape}"),
            shapes, axes,
            is_leaf=lambda x: isinstance(x, tuple) and not
            isinstance(x, jax.ShapeDtypeStruct),
        )

    def test_moe_expert_axes(self):
        cfg = get_config("deepseek-moe-16b")
        model = build_model(cfg)
        axes = model.param_logical_axes()
        expert_axes = axes["blocks"]["moe"]["experts"]["w_gate"]
        assert expert_axes == ("layers", "experts", "embed", "expert_mlp")

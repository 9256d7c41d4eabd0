"""Unit tests for the launch-layer sharding policy (no compilation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding as shd
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import DecoderLM


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    return make_test_mesh(2, 2)


def test_assign_prefers_batch_then_seq(mesh):
    def norm(spec):
        return tuple(x if not isinstance(x, tuple) or len(x) != 1 else x[0]
                     for x in tuple(spec))
    # batch divisible -> batch sharded
    assert norm(shd._assign((8, 64), mesh, [(("data",), [0, 1])])) == ("data", None)
    # batch=1 -> falls to the sequence dim (long_500k situation)
    assert norm(shd._assign((1, 64), mesh, [(("data",), [0, 1])])) == (None, "data")
    # nothing divisible -> replicated
    assert norm(shd._assign((1, 3), mesh, [(("data",), [0, 1])])) == (None, None)


def test_lead_axes_exact_vs_uneven(mesh):
    assert shd._lead_axes(8, mesh, exact=True) == ("data", "model")
    assert shd._lead_axes(3, mesh, exact=True) == ()      # 3 % 2 != 0
    assert shd._lead_axes(3, mesh, exact=False) == ("data",)  # padding ok
    assert shd._lead_axes(1, mesh, exact=False) == ()


def test_sanitize_drops_nondividing_axes(mesh):
    # vocab 32001 can't shard 2-way
    spec = shd._sanitize(P(None, "model"), (1600, 32001), mesh)
    assert spec == P(None, None)
    spec = shd._sanitize(P(None, "model"), (1600, 32000), mesh)
    assert spec == P(None, "model")


def test_param_pspecs_megatron_pairing(mesh):
    cfg = get_config("llama3_2_1b")
    model = DecoderLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    specs = shd.params_pspecs(shapes, cfg, mesh=mesh)
    # column-parallel: outputs over model; row-parallel: inputs over model
    assert specs["blocks"]["attn"]["wq"][-1] == "model"
    assert specs["blocks"]["attn"]["wo"][-2] == "model"
    assert specs["blocks"]["mlp"]["up"][-1] == "model"
    assert specs["blocks"]["mlp"]["down"][-2] == "model"
    # norms replicated (sanitize pads with Nones; all entries must be None)
    assert all(x is None for x in tuple(specs["blocks"]["ln1"]["gamma"]))


def test_param_pspecs_fsdp_threshold(mesh):
    big = get_config("nemotron_4_340b")
    model = DecoderLM(big)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    specs = shd.params_pspecs(shapes, big, mesh=mesh)
    # 2D: d_in additionally over data
    assert specs["blocks"]["attn"]["wq"][-2] == "data"
    small = get_config("llama3_2_1b")
    model_s = DecoderLM(small)
    shapes_s = jax.eval_shape(lambda: model_s.init(jax.random.PRNGKey(0)))
    specs_s = shd.params_pspecs(shapes_s, small, mesh=mesh)
    assert specs_s["blocks"]["attn"]["wq"][-2] is None


def test_cache_pspecs_gqa_and_long_context(mesh):
    from repro.configs import INPUT_SHAPES
    cfg = get_config("llama3_2_1b")
    model = DecoderLM(cfg)
    specs32 = shd.cache_pspecs(
        jax.eval_shape(lambda: model.init_cache(128, 32768)), mesh)
    def has(entry, name):
        return entry == name or entry == (name,)
    # batch over data, kv heads (8) over model (2-way ok)
    assert has(specs32["k"][1], "data")
    assert has(specs32["k"][3], "model")
    specs_long = shd.cache_pspecs(
        jax.eval_shape(lambda: model.init_cache(1, 524288)), mesh)
    # batch=1: data axes fall to the sequence dim
    assert has(specs_long["k"][2], "data")


def test_factor_sharding_hook_uneven_ok(mesh):
    hook = shd.factor_sharding_hook(mesh)
    x = jnp.zeros((5, 2, 8, 8))             # L=5 not divisible by 4
    with jax.set_mesh(mesh):
        out = jax.jit(lambda x: hook("blk/test", "a", x))(x)
    assert out.shape == x.shape
    y = jnp.zeros((3,))
    with jax.set_mesh(mesh):
        out = jax.jit(lambda y: hook("embed", "a", y))(y)  # non-blk: untouched
    assert out.shape == y.shape

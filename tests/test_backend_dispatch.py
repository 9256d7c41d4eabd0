"""Backend dispatch parity: every hot-path op must agree between the ``ref``
(jnp einsum) and ``pallas`` (interpret mode on CPU) backends — including the
blocked (lead..., nb, b, b) factor layouts, odd/padded shapes (dims that are
not tile multiples), leading layer/expert axes, and bf16 inputs — and the
two backends must train end-to-end to matching losses."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kfac
from repro.kernels import dispatch, ops, ref


def _tol(dtype):
    return 1e-4 if dtype == jnp.float32 else 0.05


# ---------------------------------------------------------------------------
# factor_sum (statistics construction, §5.2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,max_dim", [
    ((64, 48), 48),          # single block
    ((100, 33), 16),         # d not a multiple of the block size (padded)
    ((3, 40, 30), 10),       # leading layer axis
    ((2, 3, 24, 20), 8),     # two leading axes (layer x expert)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_factor_sum_parity(shape, max_dim, dtype):
    rng = np.random.RandomState(hash((shape, max_dim)) % 2**31)
    x = jnp.asarray(rng.randn(*shape), dtype)
    a = kfac.factor_sum(x, max_dim, backend="ref")
    b = kfac.factor_sum(x, max_dim, backend="pallas")
    assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32
    t = _tol(dtype)
    np.testing.assert_allclose(a, b, rtol=t, atol=t * 10)


# ---------------------------------------------------------------------------
# blocked preconditioning  U = A^-1 dW G^-1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lead,d_in,d_out,ba,bg", [
    ((), 32, 24, 32, 24),     # single block each side
    ((), 40, 30, 14, 12),     # padded blocks (dims not block multiples)
    ((3,), 40, 24, 14, 12),   # leading layer axis
    ((2, 2), 20, 16, 8, 8),   # layer x expert
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_precondition_parity(lead, d_in, d_out, ba, bg, dtype):
    rng = np.random.RandomState(hash((lead, d_in, d_out)) % 2**31)
    nba = kfac.num_blocks(d_in, ba)
    nbg = kfac.num_blocks(d_out, bg)
    ba_ = kfac.block_size(d_in, ba)
    bg_ = kfac.block_size(d_out, bg)
    dw = jnp.asarray(rng.randn(*lead, d_in, d_out), dtype)
    a_inv = jnp.asarray(rng.randn(*lead, nba, ba_, ba_), jnp.float32)
    g_inv = jnp.asarray(rng.randn(*lead, nbg, bg_, bg_), jnp.float32)
    u_ref = kfac.precondition(dw, a_inv, g_inv, backend="ref")
    u_pl = kfac.precondition(dw, a_inv, g_inv, backend="pallas")
    t = _tol(dtype)
    np.testing.assert_allclose(np.asarray(u_ref, np.float32),
                               np.asarray(u_pl, np.float32),
                               rtol=t, atol=t * 10)


def test_precondition_parity_one_sided_and_diag():
    rng = np.random.RandomState(0)
    dw = jnp.asarray(rng.randn(3, 40, 24), jnp.float32)
    a_inv = jnp.asarray(rng.randn(3, 3, 14, 14), jnp.float32)
    g_diag = jnp.asarray(rng.rand(3, 24) + 0.5, jnp.float32)
    for a, g in [(a_inv, None), (None, None), (a_inv, g_diag)]:
        u_ref = kfac.precondition(dw, a, g, backend="ref")
        u_pl = kfac.precondition(dw, a, g, backend="pallas")
        np.testing.assert_allclose(u_ref, u_pl, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# windowed attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,window", [(64, 16), (50, 13), (33, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_parity(s, window, dtype):
    rng = np.random.RandomState(s + window)
    bh, hd = 2, 16
    q = jnp.asarray(rng.randn(bh, s, hd), dtype)
    k = jnp.asarray(rng.randn(bh, s, hd), dtype)
    v = jnp.asarray(rng.randn(bh, s, hd), dtype)
    a = dispatch.swa_attention(q, k, v, window=window, backend="ref")
    b = dispatch.swa_attention(q, k, v, window=window, backend="pallas")
    t = 2e-4 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=t, atol=t)


def test_model_attention_pallas_route_matches_ref():
    """models.attention with backend="pallas" (kernel route incl. GQA repeat
    and custom-VJP wrapper) must match the chunked ref path, values AND
    gradients."""
    from repro.models.attention import attention
    rng = np.random.RandomState(3)
    b, s, h, kv, hd, w = 2, 24, 4, 2, 16, 12
    q = jnp.asarray(rng.randn(b, s, h, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv, hd), jnp.float32)
    o_ref = attention(q, k, v, window=w, backend="ref")
    o_pl = attention(q, k, v, window=w, backend="pallas")
    np.testing.assert_allclose(o_ref, o_pl, rtol=2e-4, atol=2e-4)

    f = lambda be: lambda q, k, v: jnp.sum(
        attention(q, k, v, window=w, backend=be) ** 2)
    g_ref = jax.grad(f("ref"), argnums=(0, 1, 2))(q, k, v)
    g_pl = jax.grad(f("pallas"), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_pl):
        np.testing.assert_allclose(a, b_, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# resolve() semantics + registry fallback
# ---------------------------------------------------------------------------

def test_resolve_auto_is_ref_on_cpu():
    assert jax.default_backend() != "tpu"  # test env invariant
    assert dispatch.resolve("auto", 4096) == "ref"
    assert dispatch.resolve(None, 4096) == "ref"
    assert dispatch.resolve("pallas", 8) == "pallas"
    with pytest.raises(ValueError):
        dispatch.resolve("mosaic", 8)


def test_resolve_auto_rejects_vacuous_dims():
    """all(()) is True — a dims-less "auto" would resolve to pallas on TPU
    unconditionally, so it must be an error. Explicit backends don't need
    dims (nothing to gate on)."""
    with pytest.raises(ValueError, match="at least one shape dim"):
        dispatch.resolve("auto")
    with pytest.raises(ValueError, match="at least one shape dim"):
        dispatch.resolve(None)
    assert dispatch.resolve("ref") == "ref"
    assert dispatch.resolve("pallas") == "pallas"


def test_lookup_unregistered_op_clear_error():
    with pytest.raises(KeyError, match="unregistered kernel op 'no_such'"):
        dispatch.lookup("no_such", "ref")


def test_kfac_factor_rejects_rectangular_tiles():
    """Survives python -O: a ValueError, not an assert."""
    x = jnp.zeros((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="square tiling"):
        ops.kfac_factor(x, bm=16, bn=32, interpret=True)


def test_direct_inverse_methods_degrade_pallas_to_ref():
    # eigh/cholesky are not matmul-shaped, so damped_inverse resolves them
    # to the ref callable even under backend="pallas" (bit-for-bit, and
    # reported as ref by dispatch.resolutions()); only
    # method="newton_schulz" engages the kernel
    rng = np.random.RandomState(1)
    m = rng.randn(2, 8, 8)
    f = jnp.asarray(m @ m.transpose(0, 2, 1) + 8 * np.eye(8), jnp.float32)
    for method in ("eigh", "cholesky"):
        a = dispatch.damped_inverse(f, jnp.asarray(1e-3), method=method,
                                    backend="ref")
        b = dispatch.damped_inverse(f, jnp.asarray(1e-3), method=method,
                                    backend="pallas")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert dispatch.resolutions()["damped_inverse"]["ref"] == \
        "_damped_inverse_ref"


def test_unregistered_backend_falls_back_to_ref():
    # an op with no impl for the resolved backend fails rather than run
    # ref silently (that would hide a missing kernel on the chip); the ref
    # impl stays reachable by resolving to "ref" explicitly
    def only_ref(x):
        return x + 1.0
    dispatch.register("only_ref_op", "ref", only_ref)
    try:
        with pytest.raises(KeyError, match="no 'pallas' implementation"):
            dispatch.lookup("only_ref_op", "pallas")
        assert dispatch.lookup("only_ref_op", "ref") is only_ref
    finally:
        dispatch._TABLE.pop("only_ref_op", None)


# ---------------------------------------------------------------------------
# ops.kfac_block_precond grid/padding regression (bm != bk)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,bm,bk", [(40, 16, 10), (40, 10, 16), (33, 12, 9)])
def test_block_precond_mixed_tiles_pad_to_lcm(b, bm, bk):
    """When bm != bk the pad target must be a multiple of BOTH tile sizes;
    padding to max(bm, bk) leaves the last contraction tile hanging past the
    array."""
    rng = np.random.RandomState(b)
    binv = jnp.asarray(rng.randn(2, b, b), jnp.float32)
    w = jnp.asarray(rng.randn(2, b, 24), jnp.float32)
    out = ops.kfac_block_precond(binv, w, bm=bm, bn=16, bk=bk, interpret=True)
    np.testing.assert_allclose(out, ref.block_precond_ref(binv, w),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end: NGDConfig(backend="pallas") trains and matches "ref"
# ---------------------------------------------------------------------------

def _tiny_setup(backend, arch="llama3_2_1b", **ngd_kw):
    """``ngd_kw`` forwards extra NGDConfig fields (inverse_method,
    factor_dtype, ...) so sibling suites can reuse this fixture for their
    own backend A/Bs (test_attention_grad, test_inverse_numerics)."""
    from repro.configs import get_config
    from repro.core.ngd import NGDConfig, SPNGD
    from repro.models.transformer import DecoderLM
    cfg = get_config(arch).reduced(
        head_dim=16, d_ff=64, vocab=128, sliding_window=8, kfac_max_dim=32)
    cfg = dataclasses.replace(cfg, backend=backend)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts,
                NGDConfig(damping=1e-3, backend=backend, **ngd_kw))
    state = opt.init(params)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (4, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab, (4, 16)),
                                   jnp.int32)}
    flags = {k: jnp.asarray(True) for k in opt.stat_names()}
    return model, opt, params, state, batch, flags


def _losses_jit(backend, steps=20, arch="llama3_2_1b", **ngd_kw):
    from repro.launch.train import make_train_step
    model, opt, params, state, batch, flags = _tiny_setup(backend, arch,
                                                          **ngd_kw)
    step = jax.jit(make_train_step(model, opt))
    out = []
    for _ in range(steps):
        params, state, m = step(params, state, batch, flags, 1e-3, 5e-3, 0.9)
        out.append(float(m["loss"]))
    return out


def test_train_step_backends_match_20_steps():
    l_ref = _losses_jit("ref")
    l_pl = _losses_jit("pallas")
    assert np.isfinite(l_pl).all()
    assert l_pl[-1] < l_pl[0]                    # it actually trains
    # The fused Pallas backward is numerically equivalent but not
    # bit-identical to ref (different reduction order), and this tiny
    # overfit fixture is chaotic once loss < 0.1: per-step f32 noise is
    # Lyapunov-amplified ~2x/step through the NGD preconditioner. Compare
    # the pre-chaos prefix tightly (a wrong gradient shows up at step 1),
    # then require both runs to stay trained.
    np.testing.assert_allclose(l_ref[:8], l_pl[:8], rtol=1e-3, atol=1e-3)
    assert max(l_ref[8:]) < 1.0 and max(l_pl[8:]) < 1.0


@pytest.mark.slow
def test_shardmap_train_step_backends_match():
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_shardmap_train_step
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    losses = {}
    for backend in ("ref", "pallas"):
        model, opt, params, state, batch, flags = _tiny_setup(backend)
        mesh = make_mesh((4, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            step = jax.jit(make_shardmap_train_step(model, opt, mesh))
            out = []
            for _ in range(20):
                params, state, m = step(params, state, batch, flags,
                                        1e-3, 5e-3, 0.9)
                out.append(float(m["loss"]))
        losses[backend] = out
    assert np.isfinite(losses["pallas"]).all()
    # prefix comparison: see test_train_step_backends_match_20_steps
    np.testing.assert_allclose(losses["ref"][:8], losses["pallas"][:8],
                               rtol=1e-3, atol=1e-3)
    assert max(losses["ref"][8:]) < 1.0 and max(losses["pallas"][8:]) < 1.0


def test_auto_resolves_ref_where_the_compiler_partitions(monkeypatch):
    # Mosaic kernels are not partitioned automatically: on a TPU, auto
    # picks a kernel only where the traced region is one device or a
    # shard_map manual over every mesh axis
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    mesh = make_mesh((4, 1), ("data", "model"))
    seen = {}

    def probe(tag):
        def f(x):
            seen[tag] = dispatch.resolve("auto", 256)
            return x
        return f

    x = jnp.zeros((8, 4))
    jax.jit(probe("no_mesh")).lower(x)
    with jax.set_mesh(mesh):
        jax.jit(probe("jit_on_mesh")).lower(x)
        for tag, axes in (("manual_data", {"data"}),
                          ("manual_all", {"data", "model"})):
            jax.jit(jax.shard_map(
                probe(tag), mesh=mesh, in_specs=P("data"),
                out_specs=P("data"), axis_names=axes,
                check_vma=False)).lower(x)
    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        jax.jit(probe("jit_one_device_mesh")).lower(x)
    assert seen == {"no_mesh": "pallas", "jit_on_mesh": "ref",
                    "manual_data": "ref", "manual_all": "pallas",
                    "jit_one_device_mesh": "pallas"}
    assert dispatch.resolve("pallas", 256) == "pallas"   # explicit: as asked

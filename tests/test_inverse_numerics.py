"""Stage-4 inversion test harness: blocked Newton-Schulz vs eigh.

The Newton-Schulz ``damped_inverse`` backend is the one kernel whose
numerics depend on CONDITIONING, not just shape — K-FAC at large batch
degrades exactly when factor conditioning drifts — so parity smoke is not
enough. Four layers of coverage:

* conditioning grid — parametrized spectra (log-uniform condition numbers
  1e0..1e8, near-rank-deficient, identity, tiny/huge scale) x damping
  {1e-8, 1e-3, 1e-1} x dtype {f32, bf16-in/f32-accum}: the dispatched
  inverse must stay within tolerance of the eigh oracle EVERYWHERE
  (converged blocks by contraction, pathological blocks by the eigh
  fallback), and the fallback must demonstrably trigger — and return the
  bit-exact eigh result — for the known-ill-conditioned combinations.
* op level — ref (jnp iteration) vs pallas (VMEM-resident kernel) parity
  incl. blocked layouts with leading layer/expert axes, and the
  ``M @ X ~= I`` fixed-point oracle.
* dispatch unification — a lookup spy proving both Stage-4 call sites
  (``ngd._damped_inv`` and ``kfac.damped_factor_inverses``) reach the
  inversion through ``dispatch.damped_inverse`` with the pallas impl and
  never recompute through the ref table entry on the pallas path.
* e2e — 20-step ref-eigh vs pallas-Newton-Schulz train parity (jit +
  shard_map schedules) and the fp8 ``factor_dtype`` x ``newton_schulz``
  cross-product smoke (NS consuming PR 3's dequantized stale history).
"""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kfac
from repro.kernels import dispatch, ops

NB, B = 2, 16          # blocked layout used across the grid (pads to the
                       # kernel's 128-lane tile, exercising the pad path)


def _seed(*key) -> int:
    """Process-independent seed (python's hash() is PYTHONHASHSEED-salted,
    which would unpin the empirically-pinned grid statuses below)."""
    return zlib.crc32(repr(key).encode())


def _spd_from_spectrum(spectrum, nb=NB, b=B, seed=0, lead=()):
    """SPD blocked factor with a prescribed spectrum per block."""
    rng = np.random.RandomState(seed)
    n = int(np.prod(lead, dtype=int)) * nb
    lam = np.asarray(spectrum(b), np.float64)
    qs = np.linalg.qr(rng.randn(n, b, b))[0]
    f = np.einsum("kab,kb,kcb->kac", qs, np.broadcast_to(lam, (n, b)), qs)
    return jnp.asarray(f.reshape(lead + (nb, b, b)), jnp.float32)


def _gram_from_spectrum(spectrum, nb=NB, b=B, seed=0):
    """bf16-in/f32-accum factor: the framework's actual statistics path.

    Factors are Grams of token matrices (A = X^T X with bf16 X, f32
    accumulation — kfac.factor_sum's contract), so they are PSD BY
    CONSTRUCTION no matter how X quantizes; this is what "bf16" means for
    Stage-4 inputs. (Quantizing a dense SPD matrix itself to bf16 instead
    makes small eigenvalues go negative — a different, ill-posed problem
    that the SPD guard in dispatch handles, tested separately.) The
    realized spectrum is ``spectrum`` floored at bf16 quantization of the
    token matrix (~(2^-8 ||X||)^2)."""
    rng = np.random.RandomState(seed)
    lam = np.asarray(spectrum(b), np.float64)
    out = []
    for k in range(nb):
        q = np.linalg.qr(rng.randn(b, b))[0]
        r = np.linalg.qr(rng.randn(2 * b, b))[0]      # orthonormal columns
        x = jnp.asarray(r @ np.diag(np.sqrt(lam)) @ q.T, jnp.bfloat16)
        out.append(jnp.einsum("na,nb->ab", x, x,
                              preferred_element_type=jnp.float32))
    return jnp.stack(out)


def _logspec(cond):
    return lambda b: np.logspace(0.0, -np.log10(max(cond, 1.0)), b)


SPECTRA = {
    "cond_1e0": _logspec(1e0),
    "cond_1e2": _logspec(1e2),
    "cond_1e4": _logspec(1e4),
    "cond_1e6": _logspec(1e6),
    "cond_1e8": _logspec(1e8),
    # exact zero eigenvalues: only the damping keeps it invertible
    "near_rank_def": lambda b: np.r_[np.ones(b - b // 4), np.zeros(b // 4)],
    "identity": lambda b: np.ones(b),
    # the init bound X0 = M / (||M||_1 ||M||_inf) is scale-invariant; these
    # catch any fixed-magnitude assumption (e.g. identity-valued padding)
    "tiny_scale": lambda b: 1e-12 * np.logspace(0.0, -2.0, b),
    "huge_scale": lambda b: 1e12 * np.logspace(0.0, -2.0, b),
}

# combinations whose DAMPED condition number exceeds what ns_iters=40 can
# contract in f32 (the 2^k doubling only bites after k ~ log2 of the
# squared condition number): the eigh fallback MUST carry exactly these.
# Note tiny/huge scale are absent — the norm-based init is scale-invariant,
# and with damping >= 1e-3 every spectrum here damps to kappa <= ~1e3.
# (Statuses pinned empirically; deterministic under the fixed seeds.)
FALLBACK_EXPECTED = {
    "float32": {("cond_1e6", 1e-8), ("cond_1e8", 1e-8),
                ("near_rank_def", 1e-8)},
    "bfloat16": {("cond_1e6", 1e-8), ("cond_1e8", 1e-8),
                 ("near_rank_def", 1e-8)},
}
# every other combination must converge WITHOUT the fallback (so the grid
# can't pass on the strength of eigh alone)
ALL_COMBOS = {(s, d) for s in SPECTRA for d in (1e-8, 1e-3, 1e-1)}


@pytest.mark.parametrize("damping", [1e-8, 1e-3, 1e-1])
@pytest.mark.parametrize("spectrum", sorted(SPECTRA))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conditioning_grid(spectrum, damping, dtype):
    seed = _seed(spectrum, damping)
    if dtype == jnp.bfloat16:
        f = _gram_from_spectrum(SPECTRA[spectrum], seed=seed)
    else:
        f = _spd_from_spectrum(SPECTRA[spectrum], seed=seed)
    d = jnp.asarray(damping, jnp.float32)
    # both legs hand the SAME f32 factor to both methods (the bf16 leg's
    # quantization lives in the statistics construction, per the §5.2
    # contract), so one f32-grade tolerance covers the whole grid
    eigh = dispatch.damped_inverse(f, d, method="eigh", backend="ref")
    assert eigh.dtype == jnp.float32
    ns, info = dispatch.damped_inverse(f, d, method="newton_schulz",
                                       backend="pallas", return_info=True)
    assert ns.dtype == jnp.float32 and np.isfinite(np.asarray(ns)).all()
    conv = np.asarray(info["ns_converged"])

    # the harness contract: whatever route each block took, the result
    # stays within tolerance of the eigh oracle
    scale = np.max(np.abs(np.asarray(eigh)), axis=(-1, -2), keepdims=True)
    err = np.max(np.abs(np.asarray(ns) - np.asarray(eigh)), axis=(-1, -2),
                 keepdims=True)
    assert (err <= 5e-3 * scale).all(), (spectrum, damping, err / scale)

    fallback = FALLBACK_EXPECTED[dtype.__name__]
    if (spectrum, damping) in fallback:
        # the pathological combos must actually exercise the fallback...
        assert not conv.any(), (spectrum, damping, np.asarray(info["ns_res"]))
        # ...and ship the eigh result bit-for-bit (the fallback recomputes
        # with the identical kfac.damped_inverse the oracle above used)
        np.testing.assert_array_equal(np.asarray(ns), np.asarray(eigh))
    else:
        assert conv.all(), (spectrum, damping, np.asarray(info["ns_res"]))


def test_indefinite_block_defers_to_clamped_eigh_semantics():
    """A factor whose small eigenvalues went NEGATIVE (the bf16-accumulation
    noise mode the eigh clamp exists for): Newton-Schulz would happily
    converge to the true inverse of the indefinite matrix, whose negative
    1/lambda directions the framework must not ship — the SPD guard
    (min diag(X) <= 0) must reroute the block to eigh's clamped result."""
    rng = np.random.RandomState(4)
    q = np.linalg.qr(rng.randn(B, B))[0]
    lam = np.r_[np.logspace(0, -2, B - 2), [-4e-3, -1e-2]]
    f = jnp.asarray(q @ np.diag(lam) @ q.T, jnp.float32)[None]
    d = jnp.asarray(1e-3)
    ns, info = dispatch.damped_inverse(f, d, method="newton_schulz",
                                       backend="pallas", return_info=True)
    eigh = dispatch.damped_inverse(f, d, method="eigh", backend="ref")
    assert not np.asarray(info["ns_converged"]).any()
    assert np.isposinf(np.asarray(info["ns_res"])).all()   # guard, not tol
    np.testing.assert_array_equal(np.asarray(ns), np.asarray(eigh))


def test_grid_covers_both_fallback_and_contraction():
    """Meta-guard: each dtype's grid must witness BOTH behaviours (some
    forced fallbacks, mostly contractions) or the harness proves nothing."""
    for dtype, fallback in FALLBACK_EXPECTED.items():
        assert fallback and fallback < ALL_COMBOS, dtype
        assert len(ALL_COMBOS - fallback) > len(fallback), dtype


# ---------------------------------------------------------------------------
# op level: ref iteration vs pallas kernel, fixed-point oracle, layouts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lead", [(), (3,), (2, 2)])
def test_ns_ref_vs_pallas_blocked_layouts(lead):
    f = _spd_from_spectrum(_logspec(1e2), seed=len(lead), lead=lead)
    d = jnp.asarray(1e-3)
    kw = dict(method="newton_schulz", ns_iters=40, ns_tol=1e-4)
    r, ir = dispatch.damped_inverse(f, d, backend="ref", return_info=True,
                                    **kw)
    p, ip = dispatch.damped_inverse(f, d, backend="pallas",
                                    return_info=True, **kw)
    assert r.shape == p.shape == f.shape
    assert ir["ns_res"].shape == ip["ns_res"].shape == f.shape[:-2]
    assert np.asarray(ir["ns_converged"]).all()
    assert np.asarray(ip["ns_converged"]).all()
    np.testing.assert_allclose(np.asarray(r), np.asarray(p),
                               rtol=2e-3, atol=2e-3)


def test_ns_fixed_point_oracle():
    """M @ X must reproduce I to the advertised residual — checked against
    the damped M directly, not against another inverse implementation."""
    f = _spd_from_spectrum(_logspec(1e3), seed=9)
    lam = 1e-3
    x = dispatch.damped_inverse(f, jnp.asarray(lam),
                                method="newton_schulz", backend="pallas")
    m = np.asarray(f, np.float64) + lam * np.eye(B)
    r = np.eye(B) - np.einsum("kab,kbc->kac", m, np.asarray(x, np.float64))
    res = np.sqrt((r ** 2).sum(axis=(-1, -2))) / np.sqrt(B)
    # the kernel's reported residual is rescaled to the unpadded ||I_b||_F
    # normalization (ops.ns_inverse) and upper-bounds this one, so a
    # converged block meets ns_tol in the caller's units
    assert (res <= 1e-4 + 1e-6).all(), res


def test_ns_kernel_rejects_over_vmem_blocks():
    b = ops.NS_KERNEL_MAX_DIM + 128
    with pytest.raises(ValueError, match="NS_KERNEL_MAX_DIM"):
        ops.ns_inverse(jnp.eye(b)[None], iters=kfac.NS_ITERS,
                       tol=kfac.NS_TOL, interpret=True)


def test_ns_pallas_over_vmem_blocks_use_tiled_kernel():
    """A block too large for the resident kernel's VMEM budget routes to
    the two-level tiled kernel (PR 7) — it must invert, not fail, and not
    silently fall back to the jnp reference iteration."""
    b = ops.NS_KERNEL_MAX_DIM + 128
    f = jnp.eye(b)[None] * 2.0
    x = dispatch.damped_inverse(f, jnp.asarray(0.0),
                                method="newton_schulz", backend="pallas",
                                ns_iters=12)
    np.testing.assert_allclose(np.asarray(x), np.eye(b)[None] / 2.0,
                               rtol=1e-4, atol=1e-5)


def test_ns_tiled_1536_matches_eigh_without_fallback(monkeypatch):
    """PR 7 acceptance: a 1536-dim block (1.5x the resident kernel's cap)
    runs through the TILED NS kernel — zero jnp-reference fallbacks, zero
    eigh re-solves — and matches the eigh oracle to the grid tolerance."""
    import repro.kernels.newton_schulz as ns_mod
    b = 1536
    routed = []
    monkeypatch.setattr(
        ops, "ns_inverse_tiled",
        (lambda orig: lambda m, **kw: routed.append(m.shape) or orig(m, **kw)
         )(ops.ns_inverse_tiled))
    # the jnp reference iteration must never run on this path
    monkeypatch.setattr(
        kfac, "newton_schulz_inverse",
        lambda *a, **k: pytest.fail("tiled path fell back to the jnp "
                                    "reference iteration"))
    f = _spd_from_spectrum(_logspec(1e2), nb=1, b=b, seed=7)
    d = jnp.asarray(1e-1, jnp.float32)
    ns, info = dispatch.damped_inverse(f, d, method="newton_schulz",
                                       backend="pallas", ns_iters=20,
                                       return_info=True)
    assert routed == [(1, b, b)]
    # converged in-kernel: the eigh/SPD fallback must NOT have fired
    assert np.asarray(info["ns_converged"]).all(), info["ns_res"]
    eigh = dispatch.damped_inverse(f, d, method="eigh", backend="ref")
    scale = np.max(np.abs(np.asarray(eigh)))
    err = np.max(np.abs(np.asarray(ns) - np.asarray(eigh)))
    assert err <= 5e-3 * scale, err / scale
    # two-level structure sanity: the padded dim tiles exactly (1536 = 3*512)
    assert ops._ns_tile(b) == 512 and hasattr(ns_mod, "ns_tiled_residual")


def test_damped_inverse_unknown_method_raises():
    f = jnp.eye(4)[None]
    with pytest.raises(ValueError, match="unknown inverse method"):
        dispatch.damped_inverse(f, jnp.asarray(1e-3), method="qr",
                                backend="ref")


# ---------------------------------------------------------------------------
# dispatch unification: both Stage-4 call sites go through dispatch, and the
# pallas path never recomputes through the ref table entry
# ---------------------------------------------------------------------------

def _spy_lookup(monkeypatch):
    calls = []
    orig = dispatch.lookup

    def spy(op, backend):
        fn = orig(op, backend)
        calls.append((op, backend, fn))
        return fn

    monkeypatch.setattr(dispatch, "lookup", spy)
    return calls


def test_kfac_factor_inverses_route_through_dispatch(monkeypatch):
    calls = _spy_lookup(monkeypatch)
    a = _spd_from_spectrum(_logspec(1e2), seed=1)
    g = _spd_from_spectrum(_logspec(1e1), nb=1, b=8, seed=2)
    kfac.damped_factor_inverses(a, g, 1e-3, NB * B, 8,
                                method="newton_schulz", backend="pallas")
    hits = [(op, be) for op, be, _ in calls if op == "damped_inverse"]
    assert hits == [("damped_inverse", "pallas")] * 2     # A side + G side
    # the resolved callable is the kernel impl, not the ref table entry
    assert all(fn is dispatch._damped_inverse_pallas
               for op, _, fn in calls if op == "damped_inverse")


def test_ngd_stage4_no_ref_recompute_on_pallas_path(monkeypatch):
    """A full refresh step with backend="pallas" must resolve every
    damped_inverse through the pallas table entry — zero lookups of the ref
    implementation (the analogue of test_attention_grad's fused-VJP spy)."""
    from test_ngd_optimizer import (loss_fn, fstats_fn, counts_fn, INFOS,
                                    _data, D_IN, D_H)
    from repro.core.ngd import NGDConfig, SPNGD
    calls = _spy_lookup(monkeypatch)
    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(D_IN, D_H) * 0.4, jnp.float32),
              "w2": jnp.asarray(rng.randn(D_H, 4) * 0.4, jnp.float32)}
    opt = SPNGD(loss_fn, INFOS, fstats_fn, counts_fn,
                NGDConfig(damping=1e-3, backend="pallas",
                          inverse_method="newton_schulz"))
    state = opt.init(params)
    flags = {k: jnp.asarray(True) for k in opt.stat_names()}
    jax.jit(opt.step)(params, state, _data(0), flags, 1e-3, 0.1, 0.9)
    hits = [(op, be) for op, be, _ in calls if op == "damped_inverse"]
    assert hits and all(be == "pallas" for _, be in hits)
    assert ("damped_inverse", "ref") not in [(op, be) for op, be in hits]


# ---------------------------------------------------------------------------
# e2e: 20-step ref-eigh vs pallas-Newton-Schulz train parity
# ---------------------------------------------------------------------------

def test_train_20_steps_ns_matches_eigh_jit():
    from test_backend_dispatch import _losses_jit
    l_eigh = _losses_jit("ref")                      # inverse_method="eigh"
    l_ns = _losses_jit("pallas", inverse_method="newton_schulz")
    assert np.isfinite(l_ns).all()
    assert l_ns[-1] < l_ns[0]
    # the NS preconditioner agrees with eigh to ~ns_tol, not bitwise, and
    # this overfit fixture is chaotic past ~step 8 (see
    # test_backend_dispatch): compare the pre-chaos prefix, then require
    # both runs to stay trained
    np.testing.assert_allclose(l_eigh[:8], l_ns[:8], rtol=1e-2, atol=1e-2)
    assert max(l_eigh[8:]) < 1.0 and max(l_ns[8:]) < 1.0


@pytest.mark.slow
def test_train_20_steps_ns_matches_eigh_shardmap():
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_shardmap_train_step
    from test_backend_dispatch import _tiny_setup
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    losses = {}
    for name, backend, kw in (("eigh", "ref", {}),
                              ("ns", "pallas",
                               {"inverse_method": "newton_schulz"})):
        model, opt, params, state, batch, flags = _tiny_setup(backend, **kw)
        mesh = make_mesh((4, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            step = jax.jit(make_shardmap_train_step(model, opt, mesh))
            out = []
            for _ in range(20):
                params, state, m = step(params, state, batch, flags,
                                        1e-3, 5e-3, 0.9)
                out.append(float(m["loss"]))
        losses[name] = out
    assert np.isfinite(losses["ns"]).all()
    np.testing.assert_allclose(losses["eigh"][:8], losses["ns"][:8],
                               rtol=1e-2, atol=1e-2)
    assert max(losses["eigh"][8:]) < 1.0 and max(losses["ns"][8:]) < 1.0


@pytest.mark.parametrize("factor_dtype", ["fp8_e4m3", "fp8_e5m2"])
def test_fp8_history_x_newton_schulz_smoke(factor_dtype):
    """fp8 factor history x NS inversion cross-product: the Stage-4
    recompute consumes PR 3's dequantized stale-side statistics through the
    Newton-Schulz path and still trains."""
    from test_backend_dispatch import _losses_jit
    l = _losses_jit("pallas", steps=8, inverse_method="newton_schulz",
                    factor_dtype=factor_dtype)
    assert np.isfinite(l).all()
    assert l[-1] < l[0]

"""Stage-4 distribution + refresh pipelining (ISSUE-7 acceptance criteria).

  * gather byte accounting: sym-packed f32 triangles for sharded full-kind
    factors, 0 for replicated fallbacks / non-gatherable stats, surfaced
    through the IntervalController ledger (with state_dict back-compat);
  * on a simulated 8-device mesh each device inverts ONLY its
    FactorReducer-owned chunk, asserted via the ``return_info`` owner
    vector, and the gathered preconditioner matches the replicated inverse;
  * indivisible leading dims fall back to the replicated inverse (owner
    identically -1);
  * the double buffer: a refresh at step t stages inverses that activate
    at t+1 while t consumes the old buffer; no-refresh steps keep the whole
    curvature tree bit-exact;
  * 20-step e2e loss parity, sharded vs replicated Stage-4, under the
    shard_map schedule across dense / ring_fp8 / hier and vs the plain jit
    step (the Stage-3 wire strategy must not perturb inversion ownership).
"""
import dataclasses
import os

import pytest

if "PYTEST_XDIST" not in os.environ and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import (FactorReducer, Stage4Inverter, gather_stat_bytes,
                        make_comm_config, template_gather_bytes)
from repro.core.ngd import NGDConfig, SPNGD
from repro.core.stale import IntervalController, sym_packed_bytes
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh

needs_devices = pytest.mark.skipif(len(jax.devices()) < 8,
                                   reason="needs 8 virtual devices")


# ---------------------------------------------------------------------------
# gather byte accounting (host-side, no devices needed)
# ---------------------------------------------------------------------------

def test_gather_stat_bytes_accounting():
    sym = (8, 2, 16, 16)
    t = 16 * 17 // 2
    assert gather_stat_bytes(sym, True) == 8 * 2 * t * 4   # packed triangle
    assert gather_stat_bytes(sym, True, scattered=False) == 0  # no gather
    assert gather_stat_bytes((8, 5), False) == 8 * 5 * 4   # dense f32
    # the packed pricing is exactly the f32 sym_packed storage formula
    assert gather_stat_bytes(sym, True) == sym_packed_bytes(sym, 4)


def test_template_gather_bytes_full_factors_only():
    template = {"fam": {
        "a": jax.ShapeDtypeStruct((8, 2, 16, 16), jnp.float32),
        "g": jax.ShapeDtypeStruct((8, 1, 4, 4), jnp.float32),
        "d": jax.ShapeDtypeStruct((8, 16), jnp.float32),
        "uwf": jax.ShapeDtypeStruct((8, 4, 4), jnp.float32),
    }}
    sym = lambda fam, key: key in ("a", "g", "uwf")
    out = template_gather_bytes(template, sym)
    t = 16 * 17 // 2
    assert out["fam.a"] == 8 * 2 * t * 4
    assert out["fam.g"] == 8 * 1 * (4 * 5 // 2) * 4
    # diag stats are elementwise-inverted everywhere; uwf is inverted via
    # the direct (non-sharded) path — neither gathers
    assert out["fam.d"] == 0 and out["fam.uwf"] == 0
    # non-full ("diag") a/g factors never gather either
    nonfull = template_gather_bytes(template, lambda fam, key: False)
    assert set(nonfull.values()) == {0}


@needs_devices
def test_reducer_gather_bytes_respect_scatter_decisions():
    mesh = make_mesh((4, 2), ("data", "model"))
    template = {"fam": {
        "a": jax.ShapeDtypeStruct((8, 2, 16, 16), jnp.float32),   # scatters
        "g": jax.ShapeDtypeStruct((6, 2, 16, 16), jnp.float32),   # fallback
    }}
    red = FactorReducer(mesh, template=template,
                        sym_fn=lambda fam, key: True)
    out = red.gather_bytes_per_stat()
    assert out["fam.a"] == 8 * 2 * (16 * 17 // 2) * 4
    assert out["fam.g"] == 0            # replicated inverse: nothing gathers


def test_interval_controller_gather_ledger_and_compat():
    ctrl = IntervalController(["x", "y"], bytes_per_stat={"x": 10, "y": 20},
                              gather_bytes_per_stat={"x": 100, "y": 0})
    ctrl.update(1, {"x": True, "y": True}, {"x": (0.0, 0.0),
                                            "y": (0.0, 0.0)})
    ctrl.update(2, {"x": False, "y": False}, {})
    assert ctrl.total_gather_bytes == 100
    assert ctrl.dense_gather_bytes == 200
    s = ctrl.summary()["comm"]
    assert s["total_gather_bytes"] == 100
    assert s["dense_gather_bytes"] == 200
    # round trip
    ctrl2 = IntervalController.from_state_dict(ctrl.state_dict())
    assert ctrl2.state_dict() == ctrl.state_dict()
    # pre-PR-7 checkpoint: no gather ledger keys -> resume at zero
    old = ctrl.state_dict()
    del old["total_gather_bytes"], old["dense_gather_bytes"]
    for st in old["stats"].values():
        del st["gather_bytes_per_refresh"]
    ctrl3 = IntervalController.from_state_dict(old)
    assert ctrl3.total_gather_bytes == 0
    assert ctrl3.stats["x"].gather_bytes_per_refresh == 0


def test_spngd_gather_bytes_template():
    from test_ngd_optimizer import (loss_fn, fstats_fn, counts_fn, INFOS)
    opt = SPNGD(loss_fn, INFOS, fstats_fn, counts_fn, NGDConfig())
    gb = opt.gather_bytes()
    assert set(gb) == set(opt.stat_names())
    # the tiny MLP's factors are full-kind: every a/g prices its triangle
    for name, b in gb.items():
        key = name.split(".")[-1]
        assert (b > 0) == (key in ("a", "g")), (name, b)


# ---------------------------------------------------------------------------
# shard-local inversion ownership (the 8-device acceptance criterion)
# ---------------------------------------------------------------------------

def _spd_blocks(lead, nb, b, seed=0):
    rng = np.random.RandomState(seed)
    m = rng.randn(lead, nb, b, 3 * b).astype(np.float32)
    f = np.einsum("lnbk,lnck->lnbc", m, m) / (3 * b)
    return jnp.asarray(f)


@needs_devices
def test_each_device_inverts_only_its_shard():
    """16 leading blocks over an 8-device group (manual_axes='all'): the
    gathered owner vector must show group index i produced exactly the
    contiguous chunk i — the psum_scatter(tiled=True) chunk assignment the
    Stage-3 reducer scattered with — and the gathered preconditioner must
    match the replicated inverse."""
    mesh = make_mesh((4, 2), ("data", "model"))
    lead, nb, b = 16, 2, 8
    template = {"fam": {"a": jax.ShapeDtypeStruct((lead, nb, b, b),
                                                  jnp.float32)}}
    red = FactorReducer(mesh, manual_axes="all", template=template,
                        sym_fn=lambda fam, key: True)
    assert red.ndev == 8
    inv4 = Stage4Inverter(red, method="eigh", backend="ref")
    f = _spd_blocks(lead, nb, b)
    damp = jnp.linspace(0.05, 0.2, lead).astype(jnp.float32)

    # host-side ownership map: contiguous chunks, one per group index
    np.testing.assert_array_equal(inv4.owners(lead),
                                  np.repeat(np.arange(8, dtype=np.int32), 2))

    with jax.set_mesh(mesh):
        inv, info = jax.jit(
            lambda f, d: inv4.invert(f, d, fam="fam", key="a",
                                     return_info=True))(f, damp)
    np.testing.assert_array_equal(np.asarray(info["owner"]),
                                  inv4.owners(lead))
    assert np.asarray(info["ns_converged"]).all()   # eigh: res == 0
    ref = dispatch.damped_inverse(f, damp[:, None], method="eigh",
                                  backend="ref")
    np.testing.assert_allclose(np.asarray(inv), np.asarray(ref),
                               rtol=2e-4, atol=1e-5)


@needs_devices
def test_indivisible_leading_dim_falls_back_to_replicated():
    mesh = make_mesh((4, 2), ("data", "model"))
    lead, nb, b = 6, 1, 8                    # 6 % 4 != 0: cannot scatter
    red = FactorReducer(mesh, template={"fam": {
        "a": jax.ShapeDtypeStruct((lead, nb, b, b), jnp.float32)}},
        sym_fn=lambda fam, key: True)
    inv4 = Stage4Inverter(red, method="eigh", backend="ref")
    f = _spd_blocks(lead, nb, b, seed=3)
    damp = jnp.full((lead,), 0.1, jnp.float32)
    np.testing.assert_array_equal(inv4.owners(lead),
                                  np.full((lead,), -1, np.int32))
    inv, info = inv4.invert(f, damp, fam="fam", key="a", return_info=True)
    np.testing.assert_array_equal(np.asarray(info["owner"]),
                                  np.full((lead,), -1, np.int32))
    ref = dispatch.damped_inverse(f, damp[:, None], method="eigh",
                                  backend="ref")
    np.testing.assert_array_equal(np.asarray(inv), np.asarray(ref))


# ---------------------------------------------------------------------------
# the double buffer (refresh at t activates at t+1)
# ---------------------------------------------------------------------------

def _tiny_opt(**kw):
    from test_ngd_optimizer import (loss_fn, fstats_fn, counts_fn, INFOS,
                                    _data, D_IN, D_H)
    rng = np.random.RandomState(7)
    params = {"w1": jnp.asarray(rng.randn(D_IN, D_H) * 0.4, jnp.float32),
              "w2": jnp.asarray(rng.randn(D_H, 4) * 0.4, jnp.float32)}
    opt = SPNGD(loss_fn, INFOS, fstats_fn, counts_fn, NGDConfig(**kw))
    return opt, params, opt.init(params), _data()


def _bitwise_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_double_buffer_activates_one_step_late():
    """Refresh at step 1 must stage the fresh inverses (precond_next) while
    the applied update still uses the init buffer; the fresh inverses become
    the active preconditioner at step 2."""
    opt_db, params, state_db, batch = _tiny_opt(double_buffer=True)
    opt_sb, _, state_sb, _ = _tiny_opt()
    flags = {k: jnp.asarray(True) for k in opt_db.stat_names()}
    args = (1e-3, 0.1, 0.0)

    p_db, s_db, _ = jax.jit(opt_db.step)(params, state_db, batch, flags,
                                         *args)
    p_sb, s_sb, _ = jax.jit(opt_sb.step)(params, state_sb, batch, flags,
                                         *args)
    # the staged buffer is EXACTLY the single-buffer fresh inverse...
    for fam in s_db["curv"]:
        assert _bitwise_equal(s_db["curv"][fam]["precond_next"],
                              s_sb["curv"][fam]["precond"])
        # ...while the active buffer is still the init (identity) one
        assert _bitwise_equal(s_db["curv"][fam]["precond"],
                              state_db["curv"][fam]["precond"])
    # the step-1 update therefore used the init buffer: identical to a
    # no-capture step from the init state (identity-preconditioned SGD)
    p_fast, _, _ = jax.jit(opt_db.step_fast)(params, state_db, batch, *args)
    np.testing.assert_allclose(np.asarray(p_db["w1"]),
                               np.asarray(p_fast["w1"]), rtol=2e-6,
                               atol=1e-7)

    # step 2 (fast): activation makes the staged inverses current, and the
    # applied update matches the single-buffer optimizer given the SAME
    # params/velocity (only the buffers differ between the two states)
    s_db2 = dict(s_db, velocity=s_sb["velocity"])
    p2_db, s2_db, _ = jax.jit(opt_db.step_fast)(p_sb, s_db2, batch, *args)
    p2_sb, _, _ = jax.jit(opt_sb.step_fast)(p_sb, s_sb, batch, *args)
    np.testing.assert_allclose(np.asarray(p2_db["w1"]),
                               np.asarray(p2_sb["w1"]), rtol=1e-6,
                               atol=1e-7)
    for fam in s2_db["curv"]:      # the swap persisted into the state
        assert _bitwise_equal(s2_db["curv"][fam]["precond"],
                              s2_db["curv"][fam]["precond_next"])


def test_double_buffer_no_refresh_is_bitexact():
    """With every flag off, a step must leave the whole double-buffered
    curvature tree bit-identical (the single-buffer invariant, extended)."""
    opt, params, state, batch = _tiny_opt(double_buffer=True)
    flags_on = {k: jnp.asarray(True) for k in opt.stat_names()}
    flags_off = {k: jnp.asarray(False) for k in opt.stat_names()}
    params, state, _ = jax.jit(opt.step)(params, state, batch, flags_on,
                                         1e-3, 0.1, 0.9)
    params, state, _ = jax.jit(opt.step_fast)(params, state, batch,
                                              1e-3, 0.1, 0.9)
    _, state2, _ = jax.jit(opt.step)(params, state, batch, flags_off,
                                     1e-3, 0.1, 0.9)
    assert _bitwise_equal(state2["curv"], state["curv"])


def test_upgrade_state_buffer_layouts():
    opt_sb, params, state_sb, _ = _tiny_opt()
    opt_db, _, state_db, _ = _tiny_opt(double_buffer=True)
    # single-buffer checkpoint -> double-buffer run: staged seeds active
    up = opt_db.upgrade_state(state_sb)
    for fam in up["curv"]:
        assert _bitwise_equal(up["curv"][fam]["precond_next"],
                              up["curv"][fam]["precond"])
    assert jax.tree.structure(up) == jax.tree.structure(state_db)
    # double-buffer checkpoint -> single-buffer run: staged copy dropped
    down = opt_sb.upgrade_state(state_db)
    assert jax.tree.structure(down) == jax.tree.structure(state_sb)
    # same-layout states pass through unchanged
    assert _bitwise_equal(opt_sb.upgrade_state(state_sb), state_sb)
    assert _bitwise_equal(opt_db.upgrade_state(state_db), state_db)


# ---------------------------------------------------------------------------
# e2e parity: sharded vs replicated Stage-4 (and vs plain jit)
# ---------------------------------------------------------------------------

def _llama_setup(ngd_kw):
    from repro.configs import get_config
    from repro.models.transformer import DecoderLM
    cfg = get_config("llama3_2_1b").reduced(head_dim=32, d_ff=128,
                                            vocab=256, kfac_max_dim=64)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts, NGDConfig(damping=1e-3, **ngd_kw))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (8, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab, (8, 16)),
                                   jnp.int32)}
    flags = {k: jnp.asarray(True) for k in opt.stat_names()}
    return model, opt, params, opt.init(params), batch, flags


def _losses_shardmap(strategy, steps=20, period=1, offset=0, lr=2e-3,
                     **ngd_kw):
    from repro.launch.train import (make_shardmap_fast_step,
                                    make_shardmap_train_step)
    # (2, 4): the layer axis (L=2) scatters, so Stage-4 actually shards
    mesh = make_mesh((2, 4), ("data", "model"))
    model, opt, params, state, batch, flags = _llama_setup(ngd_kw)
    with jax.set_mesh(mesh):
        comm = make_comm_config(strategy)
        step = jax.jit(make_shardmap_train_step(model, opt, mesh, comm=comm))
        # period > 1: capture on steps t % period == offset, fast steps in
        # between — the cadence train.py's loop drives (and the only legal
        # one for the chunked pipeline, whose drain rides the fast step)
        fast = (jax.jit(make_shardmap_fast_step(model, opt, mesh, comm=comm))
                if period > 1 else None)
        if ngd_kw.get("inverse_sharding"):
            assert opt.stage4 is not None       # the builder attached it
        out = []
        for t in range(steps):
            # lr gentler than the eager-refresh e2e tests: refreshing every
            # step against a one-step-stale buffer oscillates at 5e-3 on
            # this overfit fixture
            if t % period == offset:
                params, state, m = step(params, state, batch, flags,
                                        1e-3, lr, 0.9)
            else:
                params, state, m = fast(params, state, batch,
                                        1e-3, lr, 0.9)
            out.append(float(m["loss"]))
    return out


def _assert_loss_parity(a, b):
    # tight pre-chaos prefix (the shared e2e convention: this overfit
    # fixture diverges bitwise after ~8 steps), then both runs must END
    # trained — the one-step-stale buffer wobbles a few steps longer than
    # the eager refresh before settling, so the mid-run bound is on the tail
    np.testing.assert_allclose(a[:8], b[:8], rtol=2e-2, atol=2e-2)
    assert max(a[-4:]) < 1.0 and max(b[-4:]) < 1.0


@needs_devices
@pytest.mark.parametrize("strategy", [
    "dense",
    pytest.param("ring_fp8", marks=pytest.mark.slow),
    pytest.param("hier", marks=pytest.mark.slow)])
def test_e2e_sharded_matches_replicated_20_steps(strategy):
    """Sharded Stage-4 is a pure distribution of the inversion work: 20-step
    loss parity with the replicated refresh under every wire strategy."""
    repl = _losses_shardmap(strategy, double_buffer=True)
    shard = _losses_shardmap(strategy, double_buffer=True,
                             inverse_sharding=True)
    assert np.isfinite(shard).all() and shard[-1] < shard[0]
    _assert_loss_parity(repl, shard)


def _assert_pipeline_parity(base, pipe, k):
    """The pipeline-vs-inline e2e envelope. The two runs are PHASE-ALIGNED
    on activations (the inline baseline captures k steps after the pipeline,
    so fresh inverses go live on the same steps); until the first activation
    both apply identity-preconditioned SGD and must agree bitwise. From
    there the runs differ only in statistic age — the pipeline's activated
    stats are k steps staler, the algorithmic cost of hiding the refresh —
    measured at <=4% trajectory deviation on this fixture (vs the 2%
    same-age envelope), with both runs ending trained."""
    np.testing.assert_array_equal(base[:k + 2], pipe[:k + 2])
    np.testing.assert_allclose(pipe[:8], base[:8], rtol=5e-2, atol=5e-2)
    assert max(base[-4:]) < 0.2 and max(pipe[-4:]) < 0.2
    assert pipe[-1] < pipe[0] and np.isfinite(pipe).all()


@needs_devices
@pytest.mark.parametrize("strategy", [
    "dense",
    pytest.param("ring_fp8", marks=pytest.mark.slow)])
def test_e2e_chunked_pipeline_matches_double_buffer_20_steps(strategy):
    """ISSUE-10 acceptance: refresh_chunks=K at a capture-every-(K+1)-steps
    cadence tracks the inline double-buffer refresh whose activations land
    on the same steps. lr gentler still than the other e2e tests: the
    parity claim is about statistic age, so the fixture must not outrun the
    refresh cadence."""
    k = 2
    base = _losses_shardmap(strategy, period=k + 1, offset=k, lr=5e-4,
                            double_buffer=True)
    pipe = _losses_shardmap(strategy, period=k + 1, offset=0, lr=5e-4,
                            double_buffer=True, refresh_chunks=k)
    _assert_pipeline_parity(base, pipe, k)


@needs_devices
@pytest.mark.slow
def test_e2e_chunked_pipeline_with_sharded_stage4_20_steps():
    """The pipeline composes with inverse_sharding: each drain chunk's
    inversions run shard-local through Stage4Inverter (its own shard_map,
    opened from the fast step's GSPMD level) and gather per chunk."""
    k = 3
    base = _losses_shardmap("dense", period=k + 1, offset=k, lr=5e-4,
                            double_buffer=True, inverse_sharding=True)
    pipe = _losses_shardmap("dense", period=k + 1, offset=0, lr=5e-4,
                            double_buffer=True, inverse_sharding=True,
                            refresh_chunks=k)
    _assert_pipeline_parity(base, pipe, k)


@needs_devices
def test_e2e_sharded_matches_jit_20_steps():
    """...and with the plain jit schedule (replicated by construction —
    NGDConfig.inverse_sharding without a mesh is inert)."""
    from repro.launch.train import make_train_step
    model, opt, params, state, batch, flags = _llama_setup(
        {"double_buffer": True, "inverse_sharding": True})
    assert opt.stage4 is None                 # jit: nothing attaches it
    step = jax.jit(make_train_step(model, opt))
    ref = []
    for _ in range(20):
        params, state, m = step(params, state, batch, flags, 1e-3, 2e-3,
                                0.9)
        ref.append(float(m["loss"]))
    shard = _losses_shardmap("dense", double_buffer=True,
                             inverse_sharding=True)
    _assert_loss_parity(ref, shard)

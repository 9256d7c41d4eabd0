"""The 5-stage shard_map schedule (paper Algorithm 3) must be numerically
identical to the single-device / GSPMD-auto step, and its HLO must contain
the paper's collectives (reduce-scatter for factors — Stage 3).

Needs 8 virtual devices: run via conftest-selected env (see conftest.py).
"""
import os

import pytest

if "PYTEST_XDIST" not in os.environ and "XLA_FLAGS" not in os.environ:
    # only effective if jax is not yet initialized in this process
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.ngd import NGDConfig, SPNGD
from repro.launch.mesh import make_mesh
from repro.launch.train import make_train_step, make_shardmap_train_step
from repro.models.transformer import DecoderLM

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _setup(arch="llama3_2_1b"):
    # extra-reduced shapes: this file compiles every step twice (ref + sm)
    cfg = get_config(arch).reduced(head_dim=32, d_ff=128, vocab=256,
                                   kfac_max_dim=64)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts, NGDConfig(damping=1e-3))
    state = opt.init(params)
    rng = np.random.RandomState(0)
    b, s = 8, 16
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (b, s)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab, (b, s)),
                                   jnp.int32)}
    flags = {k: jnp.asarray(True) for k in opt.stat_names()}
    return model, opt, params, state, batch, flags


def _mesh():
    return make_mesh((4, 2), ("data", "model"))


@pytest.mark.parametrize("accum", [
    1, pytest.param(2, marks=pytest.mark.slow)])
def test_shardmap_matches_single_device(accum):
    model, opt, params, state, batch, flags = _setup()
    # reference: plain single-device step (microbatched the same way)
    ref_step = make_train_step(model, opt, accum=accum)
    p_ref, s_ref, m_ref = jax.jit(ref_step)(params, state, batch, flags,
                                            1e-3, 1e-2, 0.9)
    mesh = _mesh()
    with jax.set_mesh(mesh):
        sm_step = make_shardmap_train_step(model, opt, mesh, accum=accum)
        p_sm, s_sm, m_sm = jax.jit(sm_step)(params, state, batch, flags,
                                            1e-3, 1e-2, 0.9)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_sm["loss"]),
                               rtol=1e-5)

    def close(a, b, tol):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = np.abs(a).max() + 1e-6
        assert np.abs(a - b).max() < tol * scale, np.abs(a - b).max()

    # preconditioned updates involve near-singular inverses (eigh), so
    # compare with a scale-relative tolerance
    jax.tree.map(lambda a, b: close(a, b, 2e-3), p_ref, p_sm)
    jax.tree.map(lambda a, b: close(a, b, 5e-3),
                 s_ref["curv"], s_sm["curv"])


def test_shardmap_hlo_has_reduce_scatter():
    model, opt, params, state, batch, flags = _setup()
    mesh = _mesh()
    with jax.set_mesh(mesh):
        sm_step = make_shardmap_train_step(model, opt, mesh, accum=1)
        hlo = jax.jit(sm_step).lower(params, state, batch, flags,
                                     1e-3, 1e-2, 0.9).compile().as_text()
    assert "reduce-scatter" in hlo, "Stage-3 ReduceScatterV missing"


@pytest.mark.slow
def test_shardmap_loss_decreases():
    model, opt, params, state, batch, flags = _setup()
    mesh = _mesh()
    with jax.set_mesh(mesh):
        sm_step = jax.jit(make_shardmap_train_step(model, opt, mesh, accum=2))
        losses = []
        for _ in range(5):
            params, state, m = sm_step(params, state, batch, flags,
                                       1e-3, 2e-2, 0.9)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()
"""Row-sparse preconditioning of an embedding's gradient.

An embedding's gradient is nonzero only at the rows of the step's token
ids, its ``A`` factor is diagonal and ``G^-1`` acts from the right, so
``A^-1 dW G^-1`` is zero at every other row. ``SPNGD._apply_precond``
preconditions the gathered rows alone when it is given the ids and they
are fewer than the vocabulary, and scatters them into zeros. Here:

* equality with the dense path, for the ``ref`` and ``pallas`` (interpret)
  backends: repeated ids, an id whose gradient row is zero, as many ids as
  rows (the dense path), the ``accum=2`` steps, and five steps of a small
  ``DecoderLM`` through ``make_train_step`` / ``make_fast_step``;
* shapes, by a spy on ``dispatch.lookup``: the embedding's
  ``block_precond_right`` gets ``cap`` rows, not ``vocab``; the other
  families' products are unchanged; the shard_map fast step still sends
  the dense shape.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fisher import SiteInfo
from repro.core.ngd import NGDConfig, SPNGD
from repro.core.stale import IntervalController
from repro.kernels import dispatch
from repro.launch import train
from repro.models.transformer import DecoderLM

BACKENDS = ("ref", "pallas")


# ---------------------------------------------------------------------------
# one embedding family, preconditioned directly
# ---------------------------------------------------------------------------

VOCAB, D, B = 300, 64, 32          # G^-1 in two blocks of 32


def _embed_opt(backend):
    infos = {"embed": SiteInfo("embed", "embed/table", VOCAB, D, None)}
    return SPNGD(None, infos, None, None, NGDConfig(backend=backend))


def _precond(seed):
    """A diagonal ``A^-1`` and a blocked SPD ``G^-1``."""
    rng = np.random.RandomState(seed)
    a = jnp.asarray(rng.uniform(0.5, 2.0, VOCAB), jnp.float32)
    m = rng.randn(D // B, B, B).astype(np.float32)
    g = m @ np.swapaxes(m, -1, -2) / B + np.eye(B, dtype=np.float32)
    return {"a": a, "g": jnp.asarray(np.linalg.inv(g))}


def _grad(ids, zero_ids=(), seed=0, dtype=jnp.bfloat16):
    """The gradient the embedding's backward builds: the output cotangent
    of each token added into its row. Tokens of ``zero_ids`` bring a zero
    cotangent, so their rows are zero though they are among the ids."""
    gy = np.random.RandomState(seed).randn(ids.size, D).astype(np.float32)
    gy[np.isin(ids.reshape(-1), zero_ids)] = 0.0
    dw = jnp.zeros((VOCAB, D), dtype).at[ids.reshape(-1)].add(
        jnp.asarray(gy, dtype))
    return {"embed": {"table": dw}}


def _both(backend, grads, ids):
    opt = _embed_opt(backend)
    curv = {"precond": _precond(1)}
    rows = opt._apply_precond("embed", grads, curv, 1e-3, ids)
    dense = opt._apply_precond("embed", grads, curv, 1e-3)
    return rows["embed/table"], dense["embed/table"]


def _assert_same(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", ["repeated_ids", "zero_row_id"])
def test_rows_equal_dense(backend, case):
    rng = np.random.RandomState(2)
    if case == "repeated_ids":
        ids = rng.randint(0, VOCAB, (2, 12)).astype(np.int32)
        ids[1, :4] = ids[0, :4]                     # every id of 4 twice
        zero = ()
    else:
        ids = rng.choice(VOCAB, (2, 12), replace=False).astype(np.int32)
        zero = (int(ids[0, 3]),)
    grads = _grad(ids, zero)
    got, want = _both(backend, grads, jnp.asarray(ids))
    _assert_same(got, want)
    out = np.setdiff1d(np.arange(VOCAB), ids)
    assert not np.asarray(got, np.float32)[out].any()
    np.testing.assert_array_equal(np.asarray(got, np.float32)[list(zero)], 0)
    present = np.setdiff1d(ids, zero)
    assert np.abs(np.asarray(got, np.float32)[present]).min(1).max() > 0


def _spy(monkeypatch):
    """Rows ``m`` of each ``block_precond_right`` call, in call order."""
    seen = []
    orig = dispatch.lookup

    def spy(op, backend):
        fn = orig(op, backend)
        if op != "block_precond_right":
            return fn

        def record(w, binv):
            seen.append(w.shape[-3])
            return fn(w, binv)
        record.__name__ = fn.__name__
        return record

    monkeypatch.setattr(dispatch, "lookup", spy)
    return seen


@pytest.mark.parametrize("backend", BACKENDS)
def test_as_many_ids_as_rows_take_dense_path(backend, monkeypatch):
    """``cap >= d_in``: no gain from rows, so the dense product runs."""
    seen = _spy(monkeypatch)
    ids = np.random.RandomState(3).randint(0, VOCAB, (VOCAB,)).astype(
        np.int32)
    opt = _embed_opt(backend)
    assert opt.precond_rows({"embed": ids}) == {"embed": (VOCAB, VOCAB)}
    assert opt.precond_rows({"embed": ids[:40]}) == {"embed": (40, VOCAB)}
    got, want = _both(backend, _grad(ids), jnp.asarray(ids))
    assert seen == [VOCAB, VOCAB]
    _assert_same(got, want)


# ---------------------------------------------------------------------------
# whole steps of a small DecoderLM: rows against every row
# ---------------------------------------------------------------------------

class _EveryRow(DecoderLM):
    """The same model with no ids: its steps precondition every row."""

    def site_rows(self, batch):
        return {}


def _lm(cls=DecoderLM, backend="ref"):
    cfg = dataclasses.replace(get_config("qwen1_5_4b").reduced(),
                              n_layers=1, kfac_max_dim=32, backend=backend)
    return cls(cfg)


def _opt(model, backend, **kw):
    return SPNGD(model.loss, model.site_infos(), model.fstats,
                 model.site_counts,
                 NGDConfig(damping=1e-3, backend=backend, **kw))


def _batches(vocab, n, b=2, s=16):
    rng = np.random.RandomState(4)
    return [{"tokens": jnp.asarray(rng.randint(0, vocab, (b, s)), jnp.int32),
             "labels": jnp.asarray(rng.randint(0, vocab, (b, s)), jnp.int32)}
            for _ in range(n)]


def _train(model, backend, batches, accum=1, **kw):
    """Algorithm 2 with the interval pinned at 2: capture, fast, ..."""
    opt = _opt(model, backend, **kw)
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params)
    step = jax.jit(train.make_train_step(model, opt, accum=accum))
    fast = jax.jit(train.make_fast_step(model, opt, accum=accum))
    ctrl = IntervalController(opt.stat_names(), alpha=0.1, min_interval=2,
                              max_interval=2)
    kinds = []
    for t, batch in enumerate(batches, 1):
        params, state, _, flags = train.take_step(
            step, fast, ctrl, t, params, state, batch, 1e-3, 1e-2, 0.9)
        kinds.append(any(flags.values()))
    assert True in kinds and False in kinds
    return params, state


def _assert_same_tree(a, b):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("backend", BACKENDS)
def test_five_steps_equal_dense(backend):
    model = _lm(backend=backend)
    batches = _batches(model.cfg.vocab, 5)
    assert batches[0]["tokens"].size < model.cfg.vocab
    p_rows, s_rows = _train(model, backend, batches, refresh_chunks=2,
                            double_buffer=True)
    p_all, s_all = _train(_lm(_EveryRow, backend), backend, batches,
                          refresh_chunks=2, double_buffer=True)
    _assert_same_tree(p_rows, p_all)
    _assert_same_tree(s_rows["velocity"], s_all["velocity"])


@pytest.mark.parametrize("backend", BACKENDS)
def test_accum2_steps_equal_dense(backend):
    """The ids of both microbatches: their gradients are summed."""
    model = _lm(backend=backend)
    batches = _batches(model.cfg.vocab, 2, b=4, s=8)
    p_rows, _ = _train(model, backend, batches, accum=2)
    p_all, _ = _train(_lm(_EveryRow, backend), backend, batches, accum=2)
    _assert_same_tree(p_rows, p_all)


# ---------------------------------------------------------------------------
# shapes the kernels receive
# ---------------------------------------------------------------------------

def _step_args(model, opt, batch, capture):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    tail = (1e-3, 1e-2, 0.9)
    if capture:
        flags = {k: jnp.asarray(True) for k in opt.stat_names()}
        return (params, state, batch, flags) + tail
    return (params, state, batch) + tail


@pytest.mark.parametrize("capture", [True, False], ids=["train", "fast"])
def test_embedding_gets_cap_rows(capture, monkeypatch):
    seen = _spy(monkeypatch)
    batch = _batches(512, 1)[0]
    cap, calls = batch["tokens"].size, {}
    for cls in (DecoderLM, _EveryRow):
        model = _lm(cls)
        vocab = model.cfg.vocab
        opt = _opt(model, "ref")
        build = train.make_train_step if capture else train.make_fast_step
        seen.clear()
        jax.eval_shape(build(model, opt),
                       *_step_args(model, opt, batch, capture))
        calls[cls] = list(seen)
    assert cap < vocab
    assert cap in calls[DecoderLM] and vocab not in calls[DecoderLM]
    assert vocab in calls[_EveryRow] and cap not in calls[_EveryRow]
    # every other family's product is the same call
    assert ([m for m in calls[DecoderLM] if m != cap]
            == [m for m in calls[_EveryRow] if m != vocab])


def test_shardmap_fast_step_keeps_dense_shape(monkeypatch):
    """Each data shard holds part of the batch, and the summed gradient
    has rows from every shard: the shard_map step preconditions them all."""
    from repro.launch.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    seen = _spy(monkeypatch)
    model = _lm()
    opt = _opt(model, "ref")
    batch = _batches(model.cfg.vocab, 1)[0]
    mesh = make_mesh((2, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        fast = train.make_shardmap_fast_step(model, opt, mesh)
        jax.eval_shape(fast, *_step_args(model, opt, batch, False))
    assert model.cfg.vocab in seen
    assert batch["tokens"].size not in seen

"""The correctness check on the CPU, at a size a test run holds: a sound
run passes, and a run whose timed path is broken underneath, or whose
program is the reference at the control's precision, does not.

Each case drives the rest of a run (``harness.run_cell`` past the look for
a chip): weights and batches from the seed, the checked steps, a short
window, the reference. The models are the two families' configurations
cut to a few units a dimension; the limits are the cells' own.
"""

import json
import os
import time

import pytest

from chipbench import harness
from chipbench.compile_log import CompileLog

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 33 + 12345


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def tiny_cell(family: str) -> harness.Cell:
    if family == "lm-x4":
        return tiny_x4_cell()
    if family == "lm":
        cfg = _load("configs", "qwen1_5_4b-l4.json")
        cfg.update(hidden_size=64, intermediate_size=96,
                   num_attention_heads=2, num_key_value_heads=2,
                   num_hidden_layers=2, vocab_size=256,
                   torch_dtype="float32")
        traffic = {"kind": "tokens", "seqs": 2, "seq_len": 16, "accum": 1,
                   "interval": 3, "pool": 6}
        limits = _load("limits", "qwen1_5_4b-l4.t2k.r3.json")
    else:
        cfg = _load("configs", "resnet18-cifar.json")
        cfg.update(widths=[8, 16], image_size=8)
        traffic = {"kind": "images", "images": 8, "accum": 1,
                   "interval": 3, "pool": 6}
        limits = _load("limits", "resnet18-cifar.r3.json")
    # blocked factors at this size too, and steps small enough that the
    # check compares arithmetic rather than an unstable trajectory
    cfg["optimizer"] = dict(cfg["optimizer"], kfac_max_dim=32, lr=1e-5)
    return harness.Cell(name=f"tiny-{family}", chips=1, config=cfg,
                        traffic=traffic,
                        limits={k: limits[k] for k in ("loss", "grad1",
                                                       "change")
                                if k in limits},
                        end_to_end=[], per_layer=[])


def tiny_x4_cell() -> harness.Cell:
    """The four-chip LM cell at the tiny LM's widths, on four CPU devices:
    4 layers, so that every block factor scatters over ``data=4``, and 8
    sequences, 2 a device; the four-chip cell's limits."""
    cell = tiny_cell("lm")
    cfg = dict(cell.config, num_hidden_layers=4)
    limits = _load("limits", "qwen1_5_4b-l4.t2k.r3.x4.json")
    return harness.Cell(
        name="tiny-lm-x4", chips=4, config=cfg,
        traffic=dict(cell.traffic, seqs=8, mesh={"data": 4},
                     stage4="sharded"),
        limits={k: limits[k] for k in ("loss", "grad1", "change")
                if k in limits},
        end_to_end=[], per_layer=[])


def frozen(kind, fn):
    """A step that returns its state unchanged."""
    def step(params, state, batch, *rest):
        _, _, m = fn(params, state, batch, *rest)
        return params, state, m
    return step


def half_batch(kind, fn):
    """Half of the batch left out, the mean taken over the rest."""
    import jax

    def step(params, state, batch, *rest):
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return fn(params, state, half, *rest)
    return step


def _chip_index():
    import jax
    return jax.lax.axis_index("data")


def drop_shard(monkeypatch):
    """The last chip's shard of the batch left out of the gradient, loss
    and factor sums (the mean still over every shard)."""
    import jax.numpy as jnp
    from repro.comm import FactorReducer
    psum, reduce_stat = FactorReducer.psum, FactorReducer.reduce_stat

    def zeroed(self, x):
        return jnp.where(_chip_index() == self.ndev - 1, jnp.zeros_like(x), x)

    monkeypatch.setattr(FactorReducer, "psum",
                        lambda self, x: psum(self, zeroed(self, x)))
    monkeypatch.setattr(FactorReducer, "reduce_stat",
                        lambda self, fam, key, v: reduce_stat(
                            self, fam, key, zeroed(self, v)))


def no_exchange(monkeypatch):
    """The exchange between chips left out: each chip keeps its own sums
    (its own chunk of a scattered statistic)."""
    import jax
    from repro.comm import FactorReducer

    def own(self, fam, key, v):
        if not self.scatter_axes(v.shape[0]):
            return v
        chunk = v.shape[0] // self.ndev
        return jax.lax.dynamic_slice_in_dim(v, _chip_index() * chunk, chunk)

    monkeypatch.setattr(FactorReducer, "psum", lambda self, x: x)
    monkeypatch.setattr(FactorReducer, "reduce_stat", own)


def run(cell, wrap=None):
    return harness.run_cell(cell, SEED, 0.05, False,
                            t_start=time.perf_counter(),
                            compile_log=CompileLog(), device_check=False,
                            wrap_step=wrap)


@pytest.mark.parametrize("family", ["lm", "conv"])
@pytest.mark.parametrize("case", ["sound", "frozen", "half_batch"])
def test_check_decides_correct(family, case):
    wrap = {"sound": None, "frozen": frozen, "half_batch": half_batch}[case]
    result = run(tiny_cell(family), wrap)
    assert result["correct"] is (case == "sound"), result["check"]
    assert result["metrics"] == {}          # a CPU run writes no metric
    assert list(result)[-1] == "check"
    if case == "frozen":
        assert result["check"]["change"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("case", ["sound", "frozen", "half_batch",
                                  "drop_shard", "no_exchange"])
def test_check_decides_correct_on_four_devices(case, monkeypatch):
    """The four-chip cell's schedule (shard_map, reduce-scatter, Stage 4
    sharded by owner) on four CPU devices: a sound run passes; a step
    that keeps its state, half the batch, the last shard dropped from the
    sums or the exchange left out do not."""
    import jax
    assert len(jax.devices()) >= 4, "chipbench/tests/conftest.py gives 4"
    wrap = {"frozen": frozen, "half_batch": half_batch}.get(case)
    if case in ("drop_shard", "no_exchange"):
        {"drop_shard": drop_shard, "no_exchange": no_exchange}[case](
            monkeypatch)
    result = run(tiny_x4_cell(), wrap)
    assert result["correct"] is (case == "sound"), result["check"]
    assert result["device"]["count"] == 4


@pytest.mark.parametrize("family", ["lm", "conv", "lm-x4"])
def test_control_fails_the_limits(family):
    """The reference at the control's operand precision, in the program's
    place, reads over at least one limit."""
    cell = tiny_cell(family)
    sess = harness.Session(cell)
    ref = sess.reference(SEED)
    ctl = sess.reference(SEED,
                         op_dtype=cell.config["precision"]["control_operands"])
    numbers = harness.compare(ctl, ref)
    assert any(numbers[k] > v for k, v in cell.limits.items()), numbers


@pytest.mark.parametrize("family", ["lm", "conv"])
def test_one_chip_programs_are_the_programs_own(family):
    """A cell on one chip runs ``make_train_step`` / ``make_fast_step``
    jitted as they are: the harness's programs lower to the same text as
    the two built here directly, as the harness built them before it ran
    cells on several chips."""
    import jax
    import jax.numpy as jnp
    from repro.core.ngd import NGDConfig, SPNGD
    from repro.launch.train import make_fast_step, make_train_step

    cell = tiny_cell(family)
    sess = harness.Session(cell)
    prog, key = sess.prog, harness.seed_key_data(SEED)
    o = cell.config["optimizer"]
    model = cell.family.build_model(cell.config)
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts,
                NGDConfig(damping=o["damping"], backend=o["backend"],
                          inverse_method=o["inverse_method"],
                          factor_dtype=jnp.dtype(o["factor_dtype"]),
                          double_buffer=True,
                          refresh_chunks=o["refresh_chunks"]))
    own = {"step": jax.jit(make_train_step(model, opt), donate_argnums=(0, 1)),
           "fast": jax.jit(make_fast_step(model, opt), donate_argnums=(0, 1))}
    params = prog.init(key)
    state = prog.init_state(params)
    batch = sess.make_pool(key)[0]
    flags = {k: True for k in prog.opt.stat_names()}
    args = {"step": (params, state, batch, flags, prog.damping, prog.lr,
                     prog.mom),
            "fast": (params, state, batch, prog.damping, prog.lr, prog.mom)}
    for kind in ("step", "fast"):
        assert getattr(prog, kind).lower(*args[kind]).as_text() == \
            own[kind].lower(*args[kind]).as_text()
    assert prog.ref_init is prog.init and sess.ref_pool is sess.make_pool


@pytest.mark.parametrize("fault", ["drop_shard", "half_batch"])
def test_reference_faults_fail_the_four_chip_limits(fault):
    """The faults the four-chip cell's limits were read against on the chip,
    planted in the reference put in the program's place: the last shard's
    rows left out of the sums, or half the batch."""
    cell = tiny_x4_cell()
    sess = harness.Session(cell)
    numbers = harness.compare(sess.reference(SEED, fault=fault),
                              sess.reference(SEED))
    assert any(numbers[k] > v for k, v in cell.limits.items()), numbers

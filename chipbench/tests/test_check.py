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


@pytest.mark.parametrize("family", ["lm", "conv"])
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

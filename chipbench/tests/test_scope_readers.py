"""The readers of the stage shares (``metrics/spngd.*_share.py``): on a
hand-made trace whose answers are known, and on the trace recorded on a
TPU v5e before the step programs named their stages (three of them read
nothing there). And the harness's step against the program's own
``take_step``, which it copies until it calls it."""

import gzip
import json
import os
import types

import pytest

from chipbench import harness, trace
from chipbench.tests.test_check import SEED, tiny_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SHARES = ("spngd.stage1_share", "spngd.stage2_share", "spngd.update_share")


def read(metric: str, red):
    reader = harness.load_module(harness.reader_path(metric + ".lm"))
    return reader.read(types.SimpleNamespace(trace=red))


def op(name, scope, start, end):
    return trace.Op(name, scope, start, end)


CAPTURE = "jit(train_step)/spngd.stage2.capture/spngd.stage1.fwd_bwd/"
# busy 0-400 ns. Stage 1: 0-100 and the while at 200-300 with its body
# (the factor sum at 100-150 nests in Stage 1 and counts as Stage 2, with
# the history at 150-170); the update 170-200. A while and a cond wrapper and a compiler copy (no
# tf_op) are left out of the unscoped time; an unscoped op under the
# capture's ops (90-120) adds nothing to it, two more (360-390) do, and
# an op outside the step programs (390-400) does not.
HAND = trace.Reduction(window=(0, 400), host=[], ops={"/device:TPU:0": [
    op("fusion.1", CAPTURE + "jvp()/dot:", 0, 100),
    op("custom-call.2", CAPTURE + "transpose(jvp())/spngd.stage2.stats/"
       "repro.kernels.factor_sum[pallas]/pallas_call:", 100, 150),
    op("fusion.3", "jit(train_step)/spngd.stage2.history/sub:", 150, 170),
    op("fusion.4", "jit(train_step)/spngd.update/add:", 170, 200),
    op("while.5", "jit(fast_step)/spngd.stage1.fwd_bwd/jvp()/while:",
       200, 300),
    op("fusion.6", "jit(fast_step)/spngd.stage1.fwd_bwd/jvp()/while/body/"
       "dot:", 210, 290),
    op("cond.7", "", 300, 340),
    op("fusion.8", "jit(fast_step)/spngd.pipeline.drain/cond/branch_0_fun/"
       "spngd.pipeline.chunk[0/2]/spngd.stage4.inverse/x:", 305, 335),
    op("copy.9", "", 340, 360),
    op("fusion.10", "jit(fast_step)/add:", 360, 380),
    op("fusion.11", "jit(train_step)/mul:", 90, 120),
    op("fusion.12", "jit(fast_step)/mul:", 380, 390),
    op("dynamic_slice.1", "jit(dynamic_slice)/dynamic_slice:", 390, 400),
]})


@pytest.mark.parametrize("metric,share", [
    ("spngd.stage1_share", 50.0),
    ("spngd.stage2_share", 17.5),
    ("spngd.update_share", 7.5),
    ("spngd.unscoped_share", 7.5),
])
def test_shares_on_a_hand_made_trace(metric, share):
    assert HAND.busy_s == pytest.approx(400e-9)
    assert read(metric, HAND) == pytest.approx(share)
    assert read(metric, None) is None


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "qwen1_5_4b-l4.t2k.r3.trace.json.gz")
    with gzip.open(path, "rt") as f:
        return trace.reduce_events(json.load(f))


def test_shares_on_the_recorded_trace(recorded):
    """Recorded before the programs opened these scopes: the stage shares
    read nothing, and about 30% of busy time ran under no scope."""
    assert [read(m, recorded) for m in SHARES] == [None, None, None]
    assert read("spngd.unscoped_share", recorded) == \
        pytest.approx(29.984146901324693, abs=1e-9)


def test_harness_step_is_take_step():
    """``harness.train_step`` copies ``repro.launch.train.take_step``: over
    a capture and its fast steps both give the same parameters, state and
    metrics, bit for bit."""
    import jax
    import numpy as np
    from repro.launch.train import take_step

    sess = harness.Session(tiny_cell("conv"))
    prog, key = sess.prog, harness.seed_key_data(SEED)
    pool = sess.make_pool(key)

    def cycle(step):
        params = prog.init(key)
        state = prog.init_state(params)
        ctrl = prog.controller(sess.interval)
        out = []
        for t in range(1, sess.interval + 1):
            params, state, m = step(ctrl, t, params, state, pool[t - 1])
            out.append(m)
        return jax.tree.leaves((params, state, out))

    ours = cycle(lambda ctrl, t, p, s, b: harness.train_step(
        prog, ctrl, t, p, s, b, False))
    theirs = cycle(lambda ctrl, t, p, s, b: take_step(
        prog.step, prog.fast, ctrl, t, p, s, b, prog.damping, prog.lr,
        prog.mom)[:3])
    assert len(ours) == len(theirs)
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

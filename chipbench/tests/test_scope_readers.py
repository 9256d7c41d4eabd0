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


# two chips, busy 0-400 ns each. Chip 0: compute 0-200, an all-reduce
# 100-150 hidden under it, a reduce-scatter 200-300 alone (under Stage 3),
# compute 300-400. Chip 1: compute 0-250 and 300-400, an all-gather-done
# 200-300 whose first half the compute hides, and a while wrapper over all
# of it (a wrapper hides nothing).
STAGE3 = "jit(train_step)/shard_map/spngd.stage3.reduce[dense:blk/mlp_up.a]/"
TWO_CHIPS = trace.Reduction(window=(0, 400), host=[], ops={
    "/device:TPU:0": [
        op("fusion.1", "jit(train_step)/spngd.stage1.fwd_bwd/dot:", 0, 200),
        op("all-reduce.2", "jit(train_step)/shard_map/spngd.stage3.reduce/"
           "psum:", 100, 150),
        op("reduce-scatter.3", STAGE3 + "reduce_scatter:", 200, 300),
        op("fusion.4", "jit(train_step)/spngd.update/add:", 300, 400)],
    "/device:TPU:1": [
        op("while.1", "jit(fast_step)/spngd.stage1.fwd_bwd/while:", 0, 400),
        op("fusion.2", "jit(fast_step)/spngd.stage1.fwd_bwd/dot:", 0, 250),
        op("all-gather-done.3", "jit(fast_step)/spngd.stage4.gather/"
           "all_gather:", 200, 300),
        op("fusion.4", "jit(fast_step)/spngd.update/add:", 300, 400)],
})


def test_collective_shares_on_a_two_chip_trace():
    """Stage 3: 150 ns on chip 0 (its two collectives), none on chip 1.
    Exposed: the hidden all-reduce 0, the lone reduce-scatter all 100 ns,
    the half-hidden all-gather 50 ns: (100 + 50) / 2 of 400 ns busy."""
    assert TWO_CHIPS.busy_s == pytest.approx(400e-9)
    assert read("spngd.stage3_share", TWO_CHIPS) == pytest.approx(
        100.0 * 75 / 400)
    assert TWO_CHIPS.collective_s() == pytest.approx(125e-9)
    assert TWO_CHIPS.exposed_collective_s() == pytest.approx(75e-9)
    assert read("comm.exposed_share", TWO_CHIPS) == pytest.approx(
        100.0 * 75 / 400)
    assert read("comm.exposed_share", None) is None
    assert read("spngd.stage3_share", None) is None


@pytest.mark.parametrize("start,end,exposed", [
    (20, 80, 0.0),          # wholly under the compute
    (50, 150, 50.0),        # half under it
    (200, 260, 60.0),       # alone
])
def test_exposed_collective_time(start, end, exposed):
    red = trace.Reduction(window=(0, 300), host=[], ops={"/device:TPU:0": [
        op("fusion.1", "jit(fast_step)/dot:", 0, 100),
        op("all-reduce-start.2", "jit(fast_step)/psum:", start, end)]})
    assert red.exposed_collective_s() == pytest.approx(exposed * 1e-9)
    assert red.exposed_collective_s() <= red.collective_s()


@pytest.mark.parametrize("name,collective", [
    ("all-reduce.3", True), ("all-gather-start.1", True),
    ("all-gather-done", True), ("reduce-scatter.2", True),
    ("collective-permute-done.4", True), ("psum.12", True),
    ("reduce_scatter.7", True), ("async-collective-done.9", True),
    ("fusion.3", False), ("dynamic-slice_reduce_fusion.1", False),
    ("copy-done.2", False), ("while.1", False)])
def test_collectives_by_op_name(name, collective):
    """The op names of the collectives in a v5e 2x2 trace of the
    four-chip cell, XLA's and JAX's, with their async halves."""
    assert bool(trace.COLLECTIVE.match(name)) is collective


def test_collective_readers_read_nothing_on_one_chip(recorded):
    """The one-chip trace holds no collective and no Stage-3 scope."""
    assert read("comm.exposed_share", recorded) is None
    assert read("spngd.stage3_share", recorded) is None

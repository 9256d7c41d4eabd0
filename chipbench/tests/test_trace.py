"""The trace reduction: on a hand-made trace whose answers are known, and on
a trace recorded on a TPU v5e (``qwen1_5_4b-l4.t2k.r3``, the first second
of its traced window, the events' arguments cut to ``tf_op``)."""

import gzip
import json
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _x(pid, tid, ts, dur, name, tf_op=None):
    e = {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
         "name": name}
    if tf_op is not None:
        e["args"] = {"tf_op": tf_op}
    return e


# window 0.9-1.4 us; ops 1.0-1.1 (Stage 4), 1.05-1.15 (unscoped, overlaps
# it), 1.2-1.25 (factor_sum); a module span on another device line; the
# host dispatches over 0.9-1.0 and waits in the controller over 1.1-1.25
HAND = {"traceEvents": [
    {"ph": "M", "pid": 3, "name": "process_name",
     "args": {"name": "/device:TPU:0"}},
    {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
     "args": {"name": "XLA Ops"}},
    {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
     "args": {"name": "XLA Modules"}},
    {"ph": "M", "pid": 7, "name": "process_name",
     "args": {"name": "/host:CPU"}},
    _x(3, 3, 1.0, 0.1, "fusion.1", "jit(f)/spngd.stage4.precond/dot"),
    _x(3, 3, 1.05, 0.1, "copy.7"),
    _x(3, 3, 1.2, 0.05, "custom-call.3",
       "jit(f)/repro.kernels.factor_sum[pallas]/x"),
    _x(3, 2, 1.0, 0.4, "jit_fast_step(1)"),
    _x(7, 1, 0.9, 0.5, "chipbench.window"),
    _x(7, 1, 1.1, 0.15, "chipbench.controller update"),
    _x(7, 1, 0.9, 0.1, "chipbench.dispatch fast_step"),
    _x(7, 1, 0.95, 0.01, "PjitFunction(fast_step)"),
]}


@pytest.fixture(scope="module")
def hand():
    return trace.reduce_events(HAND)


def test_busy_is_the_union_of_op_intervals(hand):
    assert hand.window_s == pytest.approx(500e-9)
    assert hand.busy_s == pytest.approx(200e-9)     # 1.0-1.15, 1.2-1.25
    assert hand.idle_share == pytest.approx(0.6)


def test_scope_time_matches_the_scope_path(hand):
    assert hand.scope_s("spngd.stage4.") == pytest.approx(100e-9)
    assert hand.scope_s("repro.kernels.factor_sum[") == pytest.approx(50e-9)
    assert hand.scope_s("spngd.stage4.", "repro.kernels.") == \
        pytest.approx(150e-9)


def test_breakdown_names_ops_and_gaps(hand):
    b = hand.breakdown()
    assert b["device_ops"][0] == ["spngd.stage4.precond:fusion",
                                  pytest.approx(100e-9)]
    assert ["copy", pytest.approx(100e-9)] in b["device_ops"]
    # gaps: 1.25-1.4 (nothing annotated), 0.9-1.0 (dispatch), 1.15-1.2
    assert b["idle_gaps"][0] == ["no annotation", pytest.approx(150e-9)]
    assert b["idle_gaps"][1] == ["dispatch fast_step", pytest.approx(100e-9)]
    assert b["idle_gaps"][2] == ["controller update", pytest.approx(50e-9)]


def test_a_trace_without_the_window_is_refused():
    events = [e for e in HAND["traceEvents"]
              if e.get("name") != "chipbench.window"]
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.reduce_events({"traceEvents": events})


def test_recorded_chip_trace():
    path = os.path.join(DATA, "qwen1_5_4b-l4.t2k.r3.trace.json.gz")
    with gzip.open(path, "rt") as f:
        r = trace.reduce_events(json.load(f))
    assert list(r.ops) == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(1.0)
    assert r.busy_s == pytest.approx(0.917899415)
    assert r.scope_s("spngd.stage4.", "spngd.pipeline.chunk") == \
        pytest.approx(0.489767557)
    assert r.scope_s("repro.kernels.block_precond_left[",
                     "repro.kernels.block_precond_right[") == \
        pytest.approx(0.341081759)
    assert r.scope_s("repro.kernels.swa_attention_fwd_res[",
                     "repro.kernels.swa_attention_bwd[") == \
        pytest.approx(0.023409598)
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["dispatch train_step",
                                 pytest.approx(0.015923558)]

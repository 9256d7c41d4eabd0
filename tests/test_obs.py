"""Telemetry subsystem tests (repro.obs).

Pins the contracts the observability layer makes:
  * Span nesting/depth/parent bookkeeping and timing monotonicity.
  * JSONL schema: every event carries {v, type, t_wall}; loss floats
    round-trip bit-exactly through json.dumps/loads.
  * Disabled path is a true no-op: zero events, no file created, console
    output unchanged.
  * IntervalController.drain() is a lossless decomposition of the byte
    ledger: per-step deltas sum back to counters()/summary() exactly, and
    the drain snapshot survives a state_dict round-trip (with pre-drain
    checkpoint compat).
  * The instrumented tiny-MLP loop streams losses bit-identical to the
    returned step metrics and surfaces Stage-4 inversion info with the
    not-refreshed sentinel on keep-branch steps.
"""
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tagging
from repro.core.fisher import SiteInfo
from repro.core.ngd import NGDConfig, SPNGD
from repro.core.stale import IntervalController
from repro.core.tagging import FactorSpec
from repro.obs import MetricsLogger, Span, inverse_tally
from repro.obs import tracing

# ---------------------------------------------------------------------------
# tiny tagged MLP (mirrors tests/test_ngd_optimizer.py at toy scale)
# ---------------------------------------------------------------------------

D_IN, D_H, D_OUT, N = 6, 8, 4, 64
SPEC = FactorSpec(max_dim=64)


def loss_fn(params, fstats, batch):
    x, y = batch["x"], batch["y"]
    h = tagging.dense_site(x, params["w1"], fstats["l1"] if fstats else None, SPEC)
    h = jnp.tanh(h)
    o = tagging.dense_site(h, params["w2"], fstats["l2"] if fstats else None, SPEC)
    return jnp.mean((o - y) ** 2), {"logits": o}


def fstats_fn():
    return {"l1": tagging.make_stats(SPEC, D_IN, D_H),
            "l2": tagging.make_stats(SPEC, D_H, D_OUT)}


INFOS = {"l1": SiteInfo("dense", "w1", D_IN, D_H, SPEC),
         "l2": SiteInfo("dense", "w2", D_H, D_OUT, SPEC)}


def counts_fn(batch):
    n = batch["x"].shape[0]
    return {"l1": (n, n), "l2": (n, n)}


def _data(seed=0, n=N):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, D_IN), jnp.float32)
    w_true = rng.randn(D_IN, D_OUT)
    y = jnp.asarray(np.asarray(x) @ w_true + 0.01 * rng.randn(n, D_OUT),
                    jnp.float32)
    return {"x": x, "y": y}


def _params(seed=3):
    rng = np.random.RandomState(seed)
    return {"w1": jnp.asarray(rng.randn(D_IN, D_H) * 0.3, jnp.float32),
            "w2": jnp.asarray(rng.randn(D_H, D_OUT) * 0.3, jnp.float32)}


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_depth_parent_and_timing():
    recs = []
    with Span("outer", sink=recs.append):
        with Span("inner", sink=recs.append):
            pass
        with Span("inner2", sink=recs.append):
            pass
    # sinks fire at exit: inner, inner2, outer
    assert [r.name for r in recs] == ["inner", "inner2", "outer"]
    inner, inner2, outer = recs
    assert outer.depth == 0 and outer.parent is None
    assert inner.depth == 1 and inner.parent == "outer"
    assert inner2.depth == 1 and inner2.parent == "outer"
    # timing monotonicity: children start after the parent and fit inside it
    assert inner.start >= outer.start
    assert inner2.start >= inner.start + inner.dur
    assert inner.dur >= 0 and inner2.dur >= 0
    assert outer.dur >= (inner.dur + inner2.dur)
    assert inner.start + inner.dur <= outer.start + outer.dur


def test_span_stack_unwinds_on_exception():
    with pytest.raises(RuntimeError):
        with Span("boom"):
            raise RuntimeError("x")
    assert tracing._ACTIVE == []
    # stack is clean: a fresh span is top-level again
    with Span("after") as s:
        assert s.depth == 0 and s.parent is None


def test_stage_and_kernel_scopes_trace():
    # named_scope is trace-time metadata only — must compose with jit
    @jax.jit
    def f(x):
        with jax.named_scope(tracing.STAGE_INVERSE):
            with tracing.kernel_scope("damped_inverse", "ref"):
                return x * 2.0
    assert float(f(jnp.float32(3.0))) == 6.0


# ---------------------------------------------------------------------------
# metrics stream
# ---------------------------------------------------------------------------

def test_jsonl_schema_roundtrip(tmp_path):
    p = tmp_path / "m.jsonl"
    with MetricsLogger(str(p)) as log:
        assert log.enabled
        log.emit("run_config", arch="toy", n_params=7)
        log.log_step(1, loss=0.1234567890123, dt=0.01, lr=0.5, kind="refresh")
        log.log_step(2, loss=float(np.float32(1 / 3)), dt=0.02)
        log.console("hello world")
        assert log.events_written == 4
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert len(lines) == 4
    for evt in lines:
        assert evt["v"] == 1
        assert isinstance(evt["type"], str)
        assert isinstance(evt["t_wall"], float)
    cfg, s1, s2, con = lines
    assert cfg["type"] == "run_config" and cfg["arch"] == "toy"
    assert s1["type"] == "step" and s1["lr"] == 0.5 and s1["kind"] == "refresh"
    # shortest-repr JSON floats round-trip bit-exactly
    assert s1["loss"] == 0.1234567890123
    assert s2["loss"] == float(np.float32(1 / 3))
    for k in ("dt", "dt_ema", "dt_p50", "dt_p99"):
        assert k in s1 and k in s2
    assert s1["dt_p50"] == 0.01 and s2["dt_p99"] == 0.02
    assert con["type"] == "console" and con["text"] == "hello world"


def test_disabled_logger_is_noop(tmp_path, capsys):
    log = MetricsLogger()
    assert not log.enabled
    log.emit("step", loss=1.0)
    log.log_step(1, loss=1.0, dt=0.1)
    with log.span("phase"):
        pass
    log.console("still prints")
    assert log.events_written == 0
    assert list(tmp_path.iterdir()) == []          # no file materialized
    assert capsys.readouterr().out == "still prints\n"
    log.close()


def test_console_text_byte_identical(tmp_path, capsys):
    p = tmp_path / "m.jsonl"
    text = "step    1 loss 7.2238 lr 0.0200 refresh 21/21"
    with MetricsLogger(str(p)) as log:
        log.console(text)
    assert capsys.readouterr().out == text + "\n"   # exactly what print() did
    evt = json.loads(p.read_text().splitlines()[0])
    assert evt["type"] == "console" and evt["text"] == text


def test_logger_path_stream_exclusive_and_stream_not_owned():
    with pytest.raises(ValueError):
        MetricsLogger("x.jsonl", stream=io.StringIO())
    buf = io.StringIO()
    log = MetricsLogger(stream=buf)
    log.emit("x")
    log.close()                                     # must NOT close caller's stream
    assert not buf.closed
    assert json.loads(buf.getvalue())["type"] == "x"


def test_span_events_reach_stream():
    buf = io.StringIO()
    log = MetricsLogger(stream=buf)
    with log.span("outer"):
        with log.span("inner"):
            pass
    evts = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert [e["name"] for e in evts] == ["inner", "outer"]
    assert evts[0]["depth"] == 1 and evts[0]["parent"] == "outer"
    assert evts[1]["depth"] == 0 and evts[1]["parent"] is None
    assert all(e["type"] == "span" and e["dur"] >= 0 for e in evts)


# ---------------------------------------------------------------------------
# inversion tallies
# ---------------------------------------------------------------------------

def test_inverse_tally_sentinel_and_rollup():
    info = {
        # one block not refreshed (sentinel -1), one clean, one fallback
        "l1.a": {"ns_res": np.array([-1.0, 0.0, 0.2]),
                 "ns_converged": np.array([True, True, False])},
        # same block size -> rolls up with l1.a
        "l1.g": {"ns_res": np.array([0.05]),
                 "ns_converged": np.array([True])},
        # nothing refreshed: excluded from the by-size rollup entirely
        "l2.a": {"ns_res": np.array([-1.0]),
                 "ns_converged": np.array([True])},
    }
    out = inverse_tally(info, {"l1.a": 8, "l1.g": 8, "l2.a": 4})
    s = out["stats"]
    assert s["l1.a"] == {"b": 8, "blocks": 3, "refreshed_blocks": 2,
                         "fallback_blocks": 1, "max_res": 0.2}
    assert s["l1.g"]["refreshed_blocks"] == 1 and s["l1.g"]["fallback_blocks"] == 0
    assert s["l2.a"]["refreshed_blocks"] == 0 and s["l2.a"]["max_res"] == 0.0
    assert out["by_block_size"] == {"8": {"refreshed_blocks": 3,
                                          "fallback_blocks": 1}}
    assert json.loads(json.dumps(out)) == out       # JSON-ready


# ---------------------------------------------------------------------------
# IntervalController drain ledger
# ---------------------------------------------------------------------------

def _run_ctrl(ctrl, steps, drain_each=None):
    rng = np.random.RandomState(0)
    for t in range(1, steps + 1):
        flags = ctrl.flags(t)
        # mixed similarities so intervals both grow and shrink
        sims = {k: ((0.5, 0.5) if rng.rand() < 0.3 else (0.0, 0.0))
                for k, v in flags.items() if v}
        ctrl.update(t, flags, sims)
        if drain_each is not None:
            drain_each.append(ctrl.drain())


def test_drain_sums_to_counters_exactly():
    ctrl = IntervalController(["a", "g"], alpha=0.1,
                              bytes_per_stat={"a": 100, "g": 50},
                              wire_bytes_per_stat={"a": 60, "g": 30},
                              gather_bytes_per_stat={"a": 10, "g": 5})
    drains = []
    _run_ctrl(ctrl, 25, drains)
    totals: dict = {}
    for d in drains:
        for k, v in d.items():
            totals[k] = totals.get(k, 0) + v
    cnt = ctrl.counters()
    assert totals == cnt                            # lossless decomposition
    s = ctrl.summary()
    assert cnt["total_stat_bytes"] == s["total_stat_bytes"]
    assert cnt["total_wire_bytes"] == s["comm"]["total_wire_bytes"]
    assert cnt["total_gather_bytes"] == s["comm"]["total_gather_bytes"]
    assert cnt["refresh_events"] == sum(st.refresh_count
                                        for st in ctrl.stats.values())
    # a drain with no intervening update is all-zero
    assert set(ctrl.drain().values()) == {0}


def test_drain_snapshot_survives_state_roundtrip():
    ctrl = IntervalController(["a", "g"], alpha=0.1,
                              bytes_per_stat={"a": 100, "g": 50})
    _run_ctrl(ctrl, 8)
    ctrl.drain()                                    # snapshot mid-run
    state = json.loads(json.dumps(ctrl.state_dict()))  # through JSON, as a ckpt
    restored = IntervalController.from_state_dict(state)
    # advance both identically: drains must agree (deltas, not totals)
    for c in (ctrl, restored):
        c.update(9, c.flags(9), {k: (0.0, 0.0) for k, v in c.flags(9).items() if v})
    assert ctrl.drain() == restored.drain()


def test_drain_pre_checkpoint_compat():
    """Checkpoints written before the drain ledger existed (no "drained"
    key) must load; the first drain then re-emits the full totals."""
    ctrl = IntervalController(["a"], alpha=0.1, bytes_per_stat={"a": 100})
    _run_ctrl(ctrl, 5)
    ctrl.drain()
    state = ctrl.state_dict()
    state.pop("drained")
    restored = IntervalController.from_state_dict(state)
    assert restored.drain() == restored.counters()


def test_summary_flat_is_scalar_only():
    ctrl = IntervalController(["a"], alpha=0.1, bytes_per_stat={"a": 100},
                              wire_bytes_per_stat={"a": 60})
    _run_ctrl(ctrl, 6)
    ctrl.record_comm({"strategy": "ring", "wire_dtype": "fp8",
                      "replicated": 2, "hops": 7.5, "ok": True})
    flat = ctrl.summary_flat()
    for k, v in flat.items():
        assert isinstance(v, (int, float)) and not isinstance(v, bool), k
    assert flat["steps"] == 6
    assert flat["comm_replicated"] == 2 and flat["comm_hops"] == 7.5
    assert "comm_strategy" not in flat and "comm_ok" not in flat
    assert flat["reduction_rate"] == ctrl.reduction_rate()
    s = ctrl.summary()
    assert flat["wire_reduction_rate"] == s["comm"]["wire_reduction_rate"]
    assert json.loads(json.dumps(flat)) == flat


# ---------------------------------------------------------------------------
# instrumented end-to-end loop
# ---------------------------------------------------------------------------

def test_e2e_stream_losses_bit_identical(tmp_path):
    """10 instrumented steps: the JSONL stream's losses are bit-identical
    to the returned step metrics, drains sum to the ledger, and the
    Stage-4 inversion info carries the -1 sentinel exactly on keep-branch
    (no-refresh) families."""
    batch = _data()
    opt = SPNGD(loss_fn, INFOS, fstats_fn, counts_fn,
                NGDConfig(damping=1e-3, inverse_info=True))
    params = _params()
    state = opt.init(params)
    step_j = jax.jit(opt.step)
    stat_names = [f"{f}.{k}" for f in ("l1", "l2") for k in ("a", "g")]
    # huge alpha: everything always reads "similar", so Algorithm 2 grows
    # the intervals Fibonacci-style and the loop mixes refresh + fast steps
    ctrl = IntervalController(stat_names, alpha=1e9,
                              bytes_per_stat={n: 64 for n in stat_names})
    p = tmp_path / "m.jsonl"
    losses, refresh_kinds = [], []
    with MetricsLogger(str(p)) as log:
        for t in range(1, 11):
            flags = ctrl.flags(t)
            jflags = {k: jnp.asarray(v) for k, v in flags.items()}
            params, state, m = step_j(params, state, batch, jflags,
                                      1e-3, 0.1, 0.9)
            refreshed = any(flags.values())
            ctrl.update(t, flags, {k: (float(v[0]), float(v[1]))
                                   for k, v in m["sims"].items()} if refreshed
                        else {})
            loss = float(m["loss"])
            losses.append(loss)
            refresh_kinds.append("refresh" if refreshed else "fast")
            # sentinel contract: refreshed families carry real residuals,
            # kept families carry exactly -1 everywhere
            inv = m["inverse_info"]
            assert set(inv) == set(stat_names)
            for name, info in inv.items():
                fam = name.split(".")[0]
                fam_refreshed = any(flags[f"{fam}.{k}"] for k in ("a", "g"))
                res = np.asarray(info["ns_res"])
                if fam_refreshed:
                    assert (res >= 0.0).all()
                else:
                    assert (res == -1.0).all()
            log.log_step(t, loss=loss, dt=0.01,
                         kind=refresh_kinds[-1],
                         grad_norm=float(m["grad_norm"]),
                         update_norm=float(m["update_norm"]),
                         comm=ctrl.drain(),
                         inverse=inverse_tally(inv, {}))
        log.emit("summary", **ctrl.summary_flat())
    evts = [json.loads(l) for l in p.read_text().splitlines()]
    steps = [e for e in evts if e["type"] == "step"]
    assert len(steps) == 10
    assert [e["loss"] for e in steps] == losses     # bit-identical round-trip
    assert [e["kind"] for e in steps] == refresh_kinds
    assert "fast" in refresh_kinds and "refresh" in refresh_kinds
    # per-step comm drains sum back to the final summary totals exactly
    summary = [e for e in evts if e["type"] == "summary"][0]
    totals: dict = {}
    for e in steps:
        for k, v in e["comm"].items():
            totals[k] = totals.get(k, 0) + v
    for k, v in totals.items():
        assert summary[k] == v, k
    assert summary["steps"] == 10
    # the tally on the final step: direct eigh inverses never fall back
    last = steps[-1]["inverse"]["stats"]
    assert all(s["fallback_blocks"] == 0 for s in last.values())


def test_inverse_info_off_by_default():
    """cfg.inverse_info defaults False: the step metric tree is unchanged
    from the seed (no inverse_info key), so existing consumers see the
    exact pytree they always did."""
    batch = _data()
    opt = SPNGD(loss_fn, INFOS, fstats_fn, counts_fn, NGDConfig(damping=1e-3))
    params = _params()
    state = opt.init(params)
    flags = {k: jnp.asarray(True)
             for k in ("l1.a", "l1.g", "l2.a", "l2.g")}
    _, _, m = jax.jit(opt.step)(params, state, batch, flags, 1e-3, 0.1, 0.0)
    assert "inverse_info" not in m
    assert {"loss", "sims", "grad_norm", "update_norm"} <= set(m)


# ---------------------------------------------------------------------------
# --profile-dir window
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("period,settle,steps,window", [
    # chunked pipeline, K = 2: captures at 1, 4, 7, ...; the first refresh
    # activates at step 4, which opens the window; 3 steps close it at 7
    (3, 3, 3, (4, 6)),
    # at least 4 steps: the window runs on to the next capture boundary
    (3, 3, 4, (4, 9)),
    # inline refresh every second step: the first fast step is step 2
    (2, 0, 1, (3, 4)),
    # a longer interval: the refresh is live before the second capture
    (5, 3, 2, (6, 10)),
])
def test_profile_window_holds_whole_cycles(monkeypatch, period, settle,
                                           steps, window):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", step)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", step)))
    prof = tracing.ProfileCapture("dir", lambda t: t % period == 1 % period,
                                  steps=steps, settle=settle)
    for step in range(1, 16):
        prof.step_start(step)
        prof.step_end(step, {"loss": jnp.float32(step)})
        if prof.done:
            break
    first, last = window
    # opened before the first step of the window, closed before the
    # capture step after its last
    assert calls == [("start", first), ("stop", last + 1)]
    prof.stop()
    assert len(calls) == 2


def test_profile_window_inert_and_stopped_early(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    off = tracing.ProfileCapture(None, lambda t: True)
    for t in range(1, 10):
        off.step_start(t)
        off.step_end(t)
    assert off.done and calls == []
    # a run that ends inside the window still closes it
    prof = tracing.ProfileCapture("dir", lambda t: t % 3 == 1, steps=30,
                                  settle=3)
    for t in range(1, 8):
        prof.step_start(t)
        prof.step_end(t, {"loss": jnp.float32(t)})
    assert calls == ["start"]
    prof.stop()
    assert calls == ["start", "stop"] and prof.done

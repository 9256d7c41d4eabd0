"""Every op of the SP-NGD step programs carries an ``spngd.`` scope, and
every host phase of a step an ``spngd.host.`` span.

* Scope coverage: in the compiled CPU HLO of ``make_train_step`` and
  ``make_fast_step`` (a tiny LM and a tiny ConvNet, inline refresh and the
  chunked pipeline), every instruction that a profiler shows as a device
  op lies under an ``spngd.`` scope. The exception is a value that depends
  on no input of the step: JAX traces the loop invariants it hoists out of
  a differentiated ``scan`` (RoPE tables, attention masks) under an empty
  name stack, and they are constants of the program, not work of a stage.
* Host spans: :func:`repro.launch.train.take_step` under the profiler
  writes its ``spngd.host.*`` annotations, with their counts.
* Loop parity: ``run()`` through ``take_step`` trains bit-identically to
  the loop body it replaced.
* The compile cache keys on the scopes, so a trace names the source that
  ran.
"""
import dataclasses
import glob
import gzip
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.ngd import NGDConfig, SPNGD
from repro.core.stale import IntervalController
from repro.launch import train
from repro.models.resnet import ConvNet, ConvNetConfig
from repro.models.transformer import DecoderLM
from repro.obs import tracing

# ---------------------------------------------------------------------------
# the compiled HLO, as the profiler sees it
# ---------------------------------------------------------------------------

_HEADER = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^(?:ROOT )?%([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_NAME = re.compile(r"%([\w.\-]+)")


def _parse(hlo: str):
    """``{computation: [(name, opcode, operands, callees, op_name)]}``
    and the entry computation's name. ``callees`` pairs each called
    computation with how it is called."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        line = line.strip()
        head = _HEADER.match(line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            if head.group(1):
                entry = head.group(2)
            continue
        ins = _INSTR.match(line)
        if not ins or cur is None:
            continue
        body = line.split(", metadata=")[0]
        callees = [(how, c) for how, c in _CALLS.findall(body)]
        for group in _BRANCHES.findall(body):
            callees += [("branch", c) for c in _NAME.findall(group)]
        called = {c for _, c in callees}
        operands = [n for n in _NAME.findall(body.split(" = ", 1)[1])
                    if n not in called]
        op_name = _OP_NAME.search(line)
        cur.append((ins.group(1), ins.group(2), operands, callees,
                    op_name.group(1) if op_name else ""))
    return comps, entry


def device_ops(hlo: str):
    """``(op_name, opcode, depends_on_input)`` of every instruction outside
    fused computations and reducers: the ops a device trace shows."""
    comps, entry = _parse(hlo)
    out = []

    def walk(comp: str, params_dep: bool):
        dep = {}
        for name, opcode, operands, callees, op_name in comps[comp]:
            d = ((opcode == "parameter" and params_dep)
                 or any(dep.get(o, False) for o in operands))
            dep[name] = d
            out.append((op_name, opcode, d))
            for how, callee in callees:
                if how in ("body", "condition", "branch", "true_computation",
                           "false_computation") or (how == "calls"
                                                    and opcode == "call"):
                    walk(callee, d)

    walk(entry, True)
    return out


def _unscoped(op_name: str) -> bool:
    # a fused op's name joins its parts' names with ";", each after the
    # first relative to the first: the scope path is the first's
    return "spngd." not in op_name.split(";")[0]


# ---------------------------------------------------------------------------
# tiny programs
# ---------------------------------------------------------------------------

def _lm():
    cfg = dataclasses.replace(get_config("qwen1_5_4b").reduced(),
                              n_layers=1, kfac_max_dim=32)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    return DecoderLM(cfg), batch


def _conv():
    model = ConvNet(ConvNetConfig(widths=(4, 8), blocks_per_stage=1,
                                  kfac_max_dim=32))
    batch = {"images": jnp.zeros((2, 8, 8, 3), jnp.float32),
             "labels": jnp.zeros((2,), jnp.int32)}
    return model, batch


_HLO: dict = {}


def _compiled(kind: str, program: str, chunks: int) -> str:
    key = (kind, program, chunks)
    if key not in _HLO:
        model, batch = _lm() if kind == "lm" else _conv()
        opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                    model.site_counts,
                    NGDConfig(double_buffer=chunks > 1,
                              refresh_chunks=chunks))
        params = model.init(jax.random.PRNGKey(0))
        state = opt.init(params)
        if program == "train":
            flags = {k: jnp.asarray(True) for k in opt.stat_names()}
            fn = train.make_train_step(model, opt)
            args = (params, state, batch, flags, 1e-3, 0.1, 0.9)
        else:
            fn = train.make_fast_step(model, opt)
            args = (params, state, batch, 1e-3, 0.1, 0.9)
        _HLO[key] = jax.jit(fn).lower(*args).compile().as_text()
    return _HLO[key]


@pytest.mark.parametrize("chunks", [1, 2])
@pytest.mark.parametrize("program", ["train", "fast"])
@pytest.mark.parametrize("kind", ["lm", "conv"])
def test_every_device_op_is_scoped(kind, program, chunks):
    ops = [(n, op, dep) for n, op, dep in
           device_ops(_compiled(kind, program, chunks))
           if n.startswith("jit(")]
    unscoped = sorted({n for n, _, dep in ops if dep and _unscoped(n)})
    assert not unscoped, unscoped
    factor_sums = [n for n, _, dep in ops
                   if "repro.kernels.factor_sum[" in n and dep]
    if program == "train":
        assert factor_sums
        assert all(tracing.STAGE_STATS in n for n in factor_sums)
    else:
        assert not factor_sums
        # the forward and backward's dots (those of the differentiated loss)
        dots = [n for n, op, dep in ops if op == "dot" and "jvp(" in n
                and dep]
        assert dots
        assert all(tracing.STAGE_FWD_BWD in n for n in dots)
        assert not any(tracing.STAGE_CAPTURE in n for n, _, _ in ops)


# ---------------------------------------------------------------------------
# host spans of one step
# ---------------------------------------------------------------------------

def _tiny_run():
    model, batch = _conv()
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts,
                NGDConfig(double_buffer=True, refresh_chunks=2))
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params)
    ctrl = IntervalController(opt.stat_names(), alpha=0.1, min_interval=3,
                              max_interval=3)
    step_j = jax.jit(train.make_train_step(model, opt), donate_argnums=(0, 1))
    fast_j = jax.jit(train.make_fast_step(model, opt), donate_argnums=(0, 1))
    return opt, params, state, ctrl, step_j, fast_j, batch


def _host_spans(trace_dir):
    path, = glob.glob(f"{trace_dir}/**/*.trace.json.gz", recursive=True)
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    return [e for e in events if e.get("ph") == "X"
            and procs.get(e["pid"], "").startswith("/host:CPU")
            and str(e.get("name", "")).startswith("spngd.host.")]


def test_take_step_host_spans(tmp_path):
    opt, params, state, ctrl, step_j, fast_j, batch = _tiny_run()
    n_stats = len(opt.stat_names())
    # compile both programs outside the trace
    params, state, _, flags = train.take_step(
        step_j, fast_j, ctrl, 1, params, state, batch, 1e-3, 0.1, 0.9)
    assert any(flags.values())
    params, state, _, flags = train.take_step(
        step_j, fast_j, ctrl, 2, params, state, batch, 1e-3, 0.1, 0.9)
    assert not any(flags.values())
    kinds = {}
    for t in (3, 4):                               # a fast step, a capture
        d = tmp_path / str(t)
        with jax.profiler.trace(str(d)):
            params, state, m, flags = train.take_step(
                step_j, fast_j, ctrl, t, params, state, batch, 1e-3, 0.1,
                0.9)
            jax.block_until_ready(m)
        kinds[t] = {e["name"]: e.get("args", {}) for e in _host_spans(d)}
    fast, capture = kinds[3], kinds[4]
    assert capture[tracing.HOST_STEP]["kind"] == "capture"
    assert capture[tracing.HOST_STEP]["step_num"] == "4"
    assert capture[tracing.HOST_FLAGS]["n"] == str(n_stats)
    assert capture[tracing.HOST_SIMS]["n"] == str(2 * n_stats)
    assert {tracing.HOST_DISPATCH, tracing.HOST_CONTROLLER} <= set(capture)
    assert fast[tracing.HOST_STEP]["kind"] == "fast"
    assert fast[tracing.HOST_FLAGS]["n"] == "0"
    assert tracing.HOST_SIMS not in fast
    assert {tracing.HOST_DISPATCH, tracing.HOST_CONTROLLER} <= set(fast)


# ---------------------------------------------------------------------------
# run() through take_step is the loop it replaced
# ---------------------------------------------------------------------------

def _loop_body_before(step_j, fast_j, ctrl, t, params, state, batch, lam,
                      lr, mom):
    """``run()``'s loop body before it became :func:`take_step`."""
    flags = ctrl.flags(t)
    if any(flags.values()):
        jflags = {k: jnp.asarray(v) for k, v in flags.items()}
        params, state, m = step_j(params, state, batch, jflags, lam, lr, mom)
        ctrl.update(t, flags, {k: (float(v[0]), float(v[1]))
                               for k, v in m["sims"].items()})
    else:
        params, state, m = fast_j(params, state, batch, lam, lr, mom)
        ctrl.update(t, flags, {})
    return params, state, m, flags


def test_run_through_take_step_is_bit_identical(monkeypatch):
    args = train.build_parser().parse_args(
        ["--steps", "6", "--batch", "2", "--seq", "16",
         "--refresh-chunks", "2"])
    cfg = dataclasses.replace(get_config("llama3_2_1b").reduced(),
                              n_layers=1)

    def losses():
        recs = []
        out = train.run(cfg, args, label="test", on_step=recs.append)
        return ([(r["program"], r["loss"], r["grad_norm"]) for r in recs],
                jax.tree.leaves(out["params"]))

    now, p_now = losses()
    monkeypatch.setattr(train, "take_step", _loop_body_before)
    before, p_before = losses()
    assert [r[0] for r in now] == ["train_step", "fast_step", "fast_step",
                                   "train_step", "fast_step", "fast_step"]
    assert now == before
    for a, b in zip(p_now, p_before):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the compile cache keys on the scopes
# ---------------------------------------------------------------------------

def test_compile_cache_keys_on_metadata(monkeypatch, tmp_path):
    from repro.launch.cache import use_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        use_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          before)

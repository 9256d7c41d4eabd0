"""Kernel micro-benchmarks + end-to-end backend A/B.

Micro section: Pallas kernels vs pure-jnp oracles. On CPU the Pallas kernels
run in interpret mode (Python emulation) so their wall time is NOT indicative
of TPU performance; we report the jnp-oracle time as the timing column and
the kernel-vs-oracle max |err| as the derived column (the correctness
contract the TPU kernel must meet).

Attention-backward A/B: the retired recompute-through-ref custom VJP
(rebuilt locally as the baseline) against the fused dq/dk/dv Pallas backward
now on the training path, compared by XLA cost-analysis FLOPs of the full
gradient computation (identical forwards, so the delta is the backward) and
by wall time. The FLOP counts are the durable signal on CPU — interpret-mode
wall time is Python emulation.

E2E section: a full SP-NGD ``train_step`` timed once per dispatch backend
(``ref`` vs ``pallas``), so every PR records the step-time delta of routing
the hot paths through the kernels. ``run()`` also stashes the measurements in
``LAST_RESULTS`` for the JSON emitter in ``benchmarks.run``.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.kernels import ops, ref

# filled by run(): {"kernel.<name>": {"us": ..., "maxerr": ...},
#                   "train_step.<backend>": {"us": ..., "loss": ...}}
LAST_RESULTS: dict = {}


def _bench_train_step(backend: str, quick: bool):
    from repro.configs import get_config
    from repro.core.ngd import NGDConfig, SPNGD
    from repro.launch.train import make_train_step
    from repro.models.transformer import DecoderLM

    cfg = get_config("llama3_2_1b").reduced(
        head_dim=32, d_ff=128, vocab=256, sliding_window=8)
    cfg = dataclasses.replace(cfg, backend=backend)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts, NGDConfig(damping=1e-3, backend=backend))
    state = opt.init(params)
    rng = np.random.RandomState(0)
    b, s = (4, 16) if quick else (8, 32)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (b, s)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab, (b, s)),
                                   jnp.int32)}
    flags = {k: jnp.asarray(True) for k in opt.stat_names()}
    step = jax.jit(make_train_step(model, opt))

    def call():
        p, st, m = step(params, state, batch, flags, 1e-3, 5e-3, 0.9)
        return m["loss"]

    t = time_fn(call, warmup=1, iters=3 if quick else 5)
    loss = float(call())
    return t, loss


def _bench_obs(quick: bool):
    """A/B of the telemetry cost (repro.obs): the SAME fast-step training
    loop — per-step block_until_ready in both arms so only the logger work
    differs — with the MetricsLogger enabled (JSONL to a temp file, per-step
    events with scalar fetches + ledger drain, exactly what a
    ``--metrics-jsonl`` run pays) vs disabled (the default no-op path). The
    fast step is the cheapest step, so the ratio is the most conservative
    reading of the <3% instrumentation budget. Returns per-step us for both
    arms; alternating repetitions, medians."""
    import tempfile
    import time as _time

    from repro.configs import get_config
    from repro.core.ngd import NGDConfig, SPNGD
    from repro.core.stale import IntervalController
    from repro.launch.train import make_fast_step
    from repro.models.transformer import DecoderLM
    from repro.obs import MetricsLogger

    cfg = get_config("llama3_2_1b").reduced(
        head_dim=32, d_ff=128, vocab=256, sliding_window=8)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts, NGDConfig(damping=1e-3))
    state = opt.init(params)
    rng = np.random.RandomState(0)
    b, s = (4, 16) if quick else (8, 32)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (b, s)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab, (b, s)),
                                   jnp.int32)}
    fast = jax.jit(make_fast_step(model, opt))
    steps = 10 if quick else 20

    def loop(log):
        ctrl = IntervalController(opt.stat_names(),
                                  bytes_per_stat=opt.stat_bytes())
        none = {k: False for k in opt.stat_names()}
        p, st = params, state
        t_start = _time.perf_counter()
        for t in range(1, steps + 1):
            t0 = _time.perf_counter()
            p, st, m = fast(p, st, batch, 1e-3, 5e-3, 0.9)
            ctrl.update(t, none, {})
            jax.block_until_ready(m["loss"])
            dt = _time.perf_counter() - t0
            if log.enabled:
                log.log_step(t, loss=float(m["loss"]), dt=dt, kind="fast",
                             grad_norm=float(m["grad_norm"]),
                             update_norm=float(m["update_norm"]),
                             comm=ctrl.drain())
        return (_time.perf_counter() - t_start) * 1e6 / steps

    jax.block_until_ready(
        fast(params, state, batch, 1e-3, 5e-3, 0.9)[2]["loss"])  # compile
    off_times, on_times = [], []
    with tempfile.TemporaryDirectory() as tmp:
        for i in range(3):
            off_times.append(loop(MetricsLogger()))
            with MetricsLogger(os.path.join(tmp, f"obs_{i}.jsonl")) as log:
                on_times.append(loop(log))
    off = sorted(off_times)[1]
    on = sorted(on_times)[1]
    return {"disabled_us": off, "enabled_us": on, "ratio": on / off,
            "steps": steps}


def _bench_attn_bwd(quick: bool):
    """A/B the attention backward: recompute-through-ref VJP (the scheme
    this repo shipped before the fused kernels) vs the fused Pallas
    dq/dk/dv backward. Returns {name: {us, flops, bwd_flops}}."""
    from repro.models import attention as attn_lib

    b, s, h, kv, hd, w = ((2, 64, 4, 2, 16, 16) if quick
                          else (2, 128, 8, 2, 32, 32))
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv, hd), jnp.float32)

    # the retired scheme, rebuilt as the baseline: Pallas forward, backward
    # re-runs the whole chunked ref attention under jax.vjp
    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def recompute_attn(q, k, v, window):
        return attn_lib.attention(q, k, v, window=window, backend="pallas")

    def _fwd(q, k, v, window):
        return recompute_attn(q, k, v, window), (q, k, v)

    def _bwd(window, res, g):
        q, k, v = res
        _, vjp = jax.vjp(lambda q, k, v: attn_lib.attention(
            q, k, v, causal=True, window=window, backend="ref"), q, k, v)
        return vjp(g)

    recompute_attn.defvjp(_fwd, _bwd)

    def loss_recompute(q, k, v):
        return jnp.sum(recompute_attn(q, k, v, w) ** 2)

    def loss_fused(q, k, v):
        return jnp.sum(attn_lib.attention(q, k, v, window=w,
                                          backend="pallas") ** 2)

    out = {}
    fwd_flops = None
    for name, loss in (("recompute", loss_recompute), ("fused", loss_fused)):
        if fwd_flops is None:
            cf = jax.jit(loss).lower(q, k, v).compile()
            fwd_flops = cf.cost_analysis().get("flops", 0.0)
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        cg = g.lower(q, k, v).compile()
        flops = cg.cost_analysis().get("flops", 0.0)
        t = time_fn(g, q, k, v, warmup=1, iters=3)
        out[name] = {"us": t, "flops": flops,
                     "bwd_flops": max(flops - fwd_flops, 0.0)}
    return out


def _bench_damped_inverse(quick: bool):
    """A/B the Stage-4 inversion: ref eigh (the LAPACK/XLA factorization
    path — not matmul-shaped, the paper's non-GEMM bottleneck) vs the
    blocked Newton-Schulz Pallas kernel (matmul-only; interpret mode on
    CPU). cost-analysis FLOPs are the durable column: the NS figure counts
    real GEMM work the MXU would run, while eigh's custom-call largely
    hides from the counter — the wall-time ratio on CPU is the honest
    comparison, the FLOP column documents that NS is pure countable
    matmuls. Returns {name: {us, flops, maxerr...}}."""
    from repro.kernels import dispatch

    nb, b = (2, 64) if quick else (4, 128)
    rng = np.random.RandomState(0)
    q = np.linalg.qr(rng.randn(nb, b, b))[0]
    lam = np.logspace(0, -3, b)                       # damped kappa ~1e3
    f = jnp.asarray(np.einsum("kab,b,kcb->kac", q, lam, q), jnp.float32)
    d = jnp.asarray(1e-3)

    fns = {
        "eigh": jax.jit(lambda f, d: dispatch.damped_inverse(
            f, d, method="eigh", backend="ref")),
        "newton_schulz": jax.jit(lambda f, d: dispatch.damped_inverse(
            f, d, method="newton_schulz", backend="pallas")),
    }
    out = {}
    for name, fn in fns.items():
        cf = fn.lower(f, d).compile()
        flops = cf.cost_analysis().get("flops", 0.0)
        out[name] = {"us": time_fn(fn, f, d, warmup=1, iters=3),
                     "flops": flops}
    err = float(jnp.max(jnp.abs(fns["newton_schulz"](f, d)
                                - fns["eigh"](f, d))))
    out["newton_schulz"]["maxerr"] = err
    return out


def _bench_serve(quick: bool):
    """Serving decode A/B on the reduced llama: the seed's dense-cache
    decode step vs the flash-decode step over the fp8 ring cache.

    Baseline per the `_bench_attn_bwd` precedent (the retired scheme,
    rebuilt locally): the seed decoded through the FULL ``max_len``-padded
    dense cache every step — masked, but full FLOPs/bandwidth. This PR's
    clamp trims the live path, so the unclamped walk is reconstructed with
    a ``window=0`` config (identical compute shapes to the seed's masked
    windowed walk — the window only changes the mask, not the contraction).
    Flash arm: ring cache of capacity ``window`` + ``swa_decode``. Both
    arms time the jitted ``decode_step`` on the ref backend (repo
    convention: jnp is the reported timing column on CPU; interpret-mode
    Pallas wall time is Python emulation). Returns {name: rec}."""
    from repro.configs import get_config
    from repro.models.transformer import DecoderLM
    from repro.serve import ServeConfig, cache_bytes

    b, plen = 8, 16
    max_len, win = (2048, 128) if quick else (4096, 256)
    rng = np.random.RandomState(0)
    prompts = jnp.asarray(rng.randint(0, 256, (b, plen)), jnp.int32)

    def build(window, serve):
        cfg = get_config("llama3_2_1b").reduced(
            head_dim=32, d_ff=128, vocab=256, sliding_window=window)
        cfg = dataclasses.replace(cfg, backend="ref")
        model = DecoderLM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prefill = jax.jit(functools.partial(model.prefill, max_len=max_len,
                                            serve=serve))
        logits, cache = prefill(params, {"tokens": prompts})
        tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
        step = jax.jit(functools.partial(model.decode_step, serve=serve))
        return model, params, cache, tok, step

    _, params, cache, tok, step = build(0, None)
    t_dense = time_fn(step, params, cache, tok, warmup=1, iters=3)

    serve = ServeConfig(kv_cache="ring", kv_dtype="fp8_e4m3", backend="ref")
    model, params, cache, tok, step = build(win, serve)
    t_flash = time_fn(step, params, cache, tok, warmup=1, iters=3)

    fp8_b = cache_bytes(cache)
    f32_b = cache_bytes(model.init_cache(
        b, max_len, serve=ServeConfig(kv_cache="ring", kv_dtype="f32")))
    dense_b = cache_bytes(model.init_cache(b, max_len))
    return {
        "serve.decode_dense": {"us": t_dense, "max_len": max_len,
                               "batch": b},
        "serve.decode_flash": {"us": t_flash, "window": win, "batch": b},
        # acceptance gauge: flash decode <= 0.5x the dense walk at
        # window <= max_len/4 (here max_len/16)
        "serve.decode_flash_over_dense": {
            "us_ratio": t_flash / t_dense,
            "max_len": max_len, "window": win,
        },
        # acceptance gauge: fp8 ring payload <= 0.3x the f32 ring cache at
        # the SAME capacity (isolates the codec from the window sizing;
        # f32_dense_bytes documents the combined ring+fp8 saving)
        "serve.kv_fp8_over_f32": {
            "ratio": fp8_b / f32_b,
            "fp8_ring_bytes": fp8_b, "f32_ring_bytes": f32_b,
            "f32_dense_bytes": dense_b,
        },
    }


def _bench_in_subprocess(flag: str, local_fn, quick: bool, what: str):
    """Run a multi-device A/B body. On the CPU backend it runs in a
    SUBPROCESS with 8 virtual CPU devices so the collectives are real
    multi-device programs — setting the device count in this process would
    oversubscribe the CPU and skew every other benchmark row's timing (the
    cross-PR A/B ratios in BENCH_kernels.json must stay comparable). On a
    TPU this process already holds the chips, and a child could not get
    them: the body runs here, on the real devices. A failing child fails
    the benchmark."""
    import json
    import subprocess
    import sys

    if jax.default_backend() == "tpu":
        return local_fn(quick)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root,
                    os.environ.get("PYTHONPATH", "")) if p)
    # the child is CPU-only: it must never reach for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.kernels_bench",
         flag] + (["--quick"] if quick else []),
        env=env, cwd=root, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"{what} A/B subprocess failed "
                           f"(exit {proc.returncode}):\n{proc.stderr}")
    return json.loads(proc.stdout.splitlines()[-1])


def _bench_comm(quick: bool):
    """Stage-3 strategy A/B (repro.comm) on 8 virtual devices."""
    return _bench_in_subprocess("--comm-json", _bench_comm_local, quick,
                                "comm")


def _bench_stage4(quick: bool):
    """Stage-4 refresh A/B (replicated vs sharded inversion) on 8 virtual
    devices."""
    return _bench_in_subprocess("--stage4-json", _bench_stage4_local, quick,
                                "stage4")


def _bench_overlap(quick: bool):
    """Chunked-refresh-pipeline vs inline-refresh A/B (ISSUE-10) on 8
    virtual devices."""
    return _bench_in_subprocess("--overlap-json", _bench_overlap_local,
                                quick, "overlap")


def _bench_overlap_local(quick: bool):
    """The refresh-overlap A/B body: the reduced llama under the shard_map
    schedule, refreshing every statistic either INLINE (the double-buffer
    refresh pays Stage-2/3 + every Stage-4 inversion in one step — the
    latency spike the pipeline exists to remove) or CHUNKED over K fast
    steps (``refresh_chunks=K``: the capture step pays Stage-2/3 only, each
    drain step fuses ~1/K of the inversions + gathers).

    The tracked quantity is the PEAK per-step surcharge over the arm's own
    idle fast-step baseline across one refresh cycle — the worst step a
    training loop actually observes. Each arm measures its own baseline
    because the pipelined fast step carries the chunk switch in its program.
    Two unmeasured warmup cycles per arm flush first-execution effects
    (compile, the one extra retrace the first post-cycle state signature
    triggers, LAPACK thread spin-up) before the timed cycles.

    ``stage4.overlap_over_inline.us_ratio`` is the acceptance gauge: the
    overlapped peak must come in under 0.3x the inline spike (K=4 with a
    balanced chunk schedule predicts ~0.25x + capture cost). Returns
    {name: rec}."""
    import time

    from repro.configs import get_config
    from repro.core.ngd import NGDConfig, SPNGD
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (make_shardmap_fast_step,
                                    make_shardmap_train_step)
    from repro.models.transformer import DecoderLM

    ndev = len(jax.devices())
    chunks = 4
    reps = 2 if quick else 3
    b, s = (4, 16) if quick else (8, 16)
    if ndev >= 4 and ndev % 2 == 0:
        mesh = make_mesh((ndev // 2, 2), ("data", "model"))
    else:                                  # in-process fallback: tiny mesh
        mesh = make_mesh((ndev, 1), ("data", "model"))
    dp_n = mesh.shape["data"]
    b = max(b, dp_n)

    def build(k):
        cfg = get_config("llama3_2_1b").reduced(
            head_dim=32, d_ff=128, vocab=256, sliding_window=8)
        cfg = dataclasses.replace(cfg, backend="ref")
        model = DecoderLM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                    model.site_counts,
                    NGDConfig(damping=1e-3, backend="ref",
                              double_buffer=True, refresh_chunks=k))
        state = opt.init(params)
        step = jax.jit(make_shardmap_train_step(model, opt, mesh))
        fast = jax.jit(make_shardmap_fast_step(model, opt, mesh))
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (b, s)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.randint(0, cfg.vocab, (b, s)),
                                       jnp.int32)}
        flags = {n: jnp.asarray(True) for n in opt.stat_names()}
        return params, state, batch, flags, step, fast

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        jax.block_until_ready(out[2]["loss"])
        return (time.perf_counter() - t0) * 1e6, out

    def measure(k):
        params, state, batch, flags, step, fast = build(k)
        p, st = params, state

        def cycle():
            # one capture + k drain/flip steps + 2 guaranteed-idle steps
            nonlocal p, st
            dt, (p, st, m) = timed(step, p, st, batch, flags,
                                   1e-3, 5e-3, 0.9)
            cap = dt
            drain, idle = [], []
            for _ in range(k + 3):
                dt, (p, st, m) = timed(fast, p, st, batch, 1e-3, 5e-3, 0.9)
                if int(m.get("refresh_inflight", 0)) > 0:
                    drain.append(dt)
                else:
                    idle.append(dt)
            return cap, drain, idle

        # warmup: TWO cycles — the first compiles, the second flushes the
        # one extra retrace the first post-cycle state signature triggers
        # (weak-type stabilization) plus LAPACK thread spin-up
        cycle()
        cycle()
        caps, drains, idles, peaks = [], [], [], []
        for _ in range(reps):
            cap, drain, idle = cycle()
            caps.append(cap)
            drains.extend(drain)
            idles.extend(idle)
            peaks.append(max([cap] + drain) if drain else cap)
        base = float(np.median(idles))
        # min over reps of the per-cycle peak: still a true observation of
        # the worst step in a cycle, but robust to a background process
        # landing on one rep (max-of-noisy-samples inflates under load)
        return {"refresh_us": float(np.median(caps)),
                "drain_us": float(np.median(drains)) if drains else 0.0,
                "fast_us": base,
                "peak_surcharge_us": max(float(np.min(peaks)) - base, 1.0)}

    inline = measure(1)
    pipe = measure(chunks)
    ratio = pipe["peak_surcharge_us"] / inline["peak_surcharge_us"]
    return {
        "stage4.refresh_inline_spike": {
            "us": inline["peak_surcharge_us"],
            "step_us": inline["refresh_us"], "fast_us": inline["fast_us"],
            "devices": ndev,
        },
        "stage4.refresh_overlapped_peak": {
            "us": pipe["peak_surcharge_us"], "chunks": chunks,
            "capture_us": pipe["refresh_us"], "drain_us": pipe["drain_us"],
            "fast_us": pipe["fast_us"], "devices": ndev,
        },
        # acceptance gauge: overlapped per-step overhead < 0.3x the inline
        # refresh spike
        "stage4.overlap_over_inline": {
            "us_ratio": ratio, "chunks": chunks, "devices": ndev,
        },
    }


def _bench_comm_local(quick: bool):
    """The comm A/B body: reduce one synthetic raw-stats tree over every
    available device with each strategy under shard_map, reporting wall
    time, max |err| vs the dense psum_scatter baseline, and the reducer's
    wire-byte accounting (the durable column on CPU — wall time here is
    interpret-mode collectives over virtual devices). Returns {name: rec}."""
    from jax.sharding import PartitionSpec as P

    from repro.comm import FactorReducer, make_comm_config
    from repro.launch.mesh import make_mesh

    ndev = len(jax.devices())
    mesh = make_mesh((ndev,), ("data",))
    nb, b = (2, 32) if quick else (4, 64)
    lead = 2 * ndev                      # scatters over the data axis
    template = {"fam": {
        "a": jax.ShapeDtypeStruct((lead, nb, b, b), jnp.float32),
        "d": jax.ShapeDtypeStruct((lead, nb * b), jnp.float32),
    }}
    rng = np.random.RandomState(0)
    f = rng.randn(ndev, lead, nb, b, b).astype(np.float32)
    raw_all = {"fam": {
        "a": jnp.asarray(f + np.swapaxes(f, -1, -2)),
        "d": jnp.asarray(rng.randn(ndev, lead, nb * b), np.float32) ** 2,
    }}

    # hier models the 8 virtual devices as 2 hosts x (ndev/2) devices so
    # both levels (intra psum_scatter + inter fp8 ring) run
    dph = max(ndev // 2, 1)
    cfgs = {
        "dense": make_comm_config("dense"),
        "ring": make_comm_config("ring"),
        "ring_fp8": make_comm_config("ring_fp8"),
        "hier": make_comm_config("hier", devices_per_host=dph),
    }
    out = {}
    results = {}
    for strat, cfg in cfgs.items():
        red = FactorReducer(mesh, comm=cfg, template=template,
                            sym_fn=lambda fam, key: key == "a")

        def body(raw):
            return red.reduce(jax.tree.map(lambda x: x[0], raw))

        in_specs = jax.tree.map(lambda _: P("data"), raw_all)
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(in_specs,),
            out_specs=red.out_specs(), axis_names={"data"},
            check_vma=False))
        t = time_fn(fn, raw_all, warmup=1, iters=3)
        results[strat] = jax.tree.map(np.asarray, fn(raw_all))
        out[f"comm.reduce_{strat}"] = {
            "us": t,
            "wire_bytes": sum(red.wire_bytes_per_stat().values()),
        }
        if strat == "hier":
            levels = red.wire_bytes_per_stat_levels().values()
            out["comm.reduce_hier"]["intra_wire_bytes"] = sum(
                i for i, _ in levels)
            out["comm.reduce_hier"]["inter_wire_bytes"] = sum(
                j for _, j in levels)
    for strat in ("ring", "ring_fp8", "hier"):
        err = max(float(np.max(np.abs(a - d))) for a, d in zip(
            jax.tree.leaves(results[strat]),
            jax.tree.leaves(results["dense"])))
        out[f"comm.reduce_{strat}"]["maxerr_vs_dense"] = err
    wd = out["comm.reduce_dense"]["wire_bytes"]
    out["comm.ring_vs_dense"] = {
        "wire_ratio": out["comm.reduce_ring"]["wire_bytes"] / wd,
        "us_ratio": (out["comm.reduce_ring"]["us"]
                     / out["comm.reduce_dense"]["us"]),
        "maxerr": out["comm.reduce_ring"]["maxerr_vs_dense"],
        "devices": ndev,
    }
    # acceptance gauge: fp8 wire <= 0.3x the dense f32 collective payload
    out["comm.wire_fp8_over_f32"] = {
        "ratio": out["comm.reduce_ring_fp8"]["wire_bytes"] / wd,
        "fp8_wire_bytes": out["comm.reduce_ring_fp8"]["wire_bytes"],
        "f32_dense_wire_bytes": wd,
        "maxerr": out["comm.reduce_ring_fp8"]["maxerr_vs_dense"],
    }
    # acceptance gauge: hier's inter-host level <= 0.2x dense f32
    out["comm.hier_inter_over_dense"] = {
        "ratio": out["comm.reduce_hier"]["inter_wire_bytes"] / wd,
        "inter_wire_bytes": out["comm.reduce_hier"]["inter_wire_bytes"],
        "intra_wire_bytes": out["comm.reduce_hier"]["intra_wire_bytes"],
        "f32_dense_wire_bytes": wd,
        "devices_per_host": dph,
        "maxerr": out["comm.reduce_hier"]["maxerr_vs_dense"],
    }

    # fused: the reducer consumes PRE-PACKED wire payloads (what the fused
    # SYRK epilogue emits); quantize once per source here, exactly as the
    # kernel would, then reduce the {"payload","scale"} tree
    from repro import quant
    from repro.core import kfac
    pay, sc = quant.quantize_rows(
        kfac.sym_pack(raw_all["fam"]["a"]), "e4m3", "fp32")
    raw_wire = {"fam": {"a": {"payload": pay, "scale": sc},
                        "d": raw_all["fam"]["d"]}}
    template_w = {"fam": {
        "a": {"payload": jax.ShapeDtypeStruct(pay.shape[1:], pay.dtype),
              "scale": jax.ShapeDtypeStruct(sc.shape[1:], sc.dtype)},
        "d": template["fam"]["d"],
    }}
    red = FactorReducer(mesh, comm=make_comm_config("fused"),
                        template=template_w,
                        sym_fn=lambda fam, key: key == "a")

    def body_w(raw):
        return red.reduce(jax.tree.map(lambda x: x[0], raw))

    in_specs = jax.tree.map(lambda _: P("data"), raw_wire)
    fn = jax.jit(jax.shard_map(
        body_w, mesh=mesh, in_specs=(in_specs,),
        out_specs=red.out_specs(), axis_names={"data"}, check_vma=False))
    t = time_fn(fn, raw_wire, warmup=1, iters=3)
    res = jax.tree.map(np.asarray, fn(raw_wire))
    err = max(float(np.max(np.abs(a - d))) for a, d in zip(
        jax.tree.leaves(res), jax.tree.leaves(results["dense"])))
    out["comm.reduce_fused"] = {
        "us": t,
        "wire_bytes": sum(red.wire_bytes_per_stat().values()),
        "maxerr_vs_dense": err,
    }
    return out


def _bench_stage4_local(quick: bool):
    """The Stage-4 A/B body: invert one scattered stack of SPD factor
    blocks with the pre-PR-7 refresh (every device redundantly inverts the
    FULL stack — modelled as a shard_map over a replicated operand, which
    is exactly what the monolithic refresh compiled to) vs the sharded
    ``Stage4Inverter`` refresh (each device inverts only its
    ``FactorReducer``-owned chunk, then all-gathers the sym-packed f32
    preconditioners). The wall-clock ratio is the acceptance gauge: the
    sharded refresh does 1/p of the eigh work per device, so it must come
    in well under the replicated baseline even after paying for the
    gather. Returns {name: rec}."""
    import functools

    from jax.sharding import PartitionSpec as P

    from repro.comm import FactorReducer, Stage4Inverter, make_comm_config
    from repro.kernels import dispatch
    from repro.launch.mesh import make_mesh

    ndev = len(jax.devices())
    mesh = make_mesh((ndev,), ("data",))
    lead, b = (ndev, 48) if quick else (2 * ndev, 96)
    rng = np.random.RandomState(0)
    q = np.linalg.qr(rng.randn(lead, b, b))[0]
    lam = np.logspace(0, -3, b)                       # damped kappa ~1e3
    f = jnp.asarray(np.einsum("kab,b,kcb->kac", q, lam, q), jnp.float32)
    damp = jnp.full((lead,), 1e-3, jnp.float32)

    template = {"fam": {"a": jax.ShapeDtypeStruct((lead, b, b),
                                                  jnp.float32)}}
    red = FactorReducer(mesh, comm=make_comm_config("dense"),
                        template=template, sym_fn=lambda fam, key: True)
    inv4 = Stage4Inverter(red, method="eigh", backend="ref")

    def repl_body(s, d):
        # d (lead,) already matches the 3-D stat's batch dims
        return dispatch.damped_inverse(s, d, method="eigh", backend="ref")

    repl = jax.jit(jax.shard_map(
        repl_body, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        axis_names={"data"}, check_vma=False))
    shard = jax.jit(functools.partial(inv4.invert, fam="fam", key="a"))

    t_repl = time_fn(repl, f, damp, warmup=1, iters=3)
    t_shard = time_fn(shard, f, damp, warmup=1, iters=3)
    err = float(jnp.max(jnp.abs(shard(f, damp) - repl(f, damp))))
    gather = sum(red.gather_bytes_per_stat().values())
    return {
        "stage4.refresh_replicated": {"us": t_repl, "devices": ndev},
        "stage4.refresh_sharded": {"us": t_shard, "devices": ndev,
                                   "gather_bytes": gather,
                                   "maxerr_vs_replicated": err},
        # acceptance gauge: sharded refresh wall clock < 0.6x replicated
        "stage4.sharded_over_replicated": {
            "us_ratio": t_shard / t_repl,
            "devices": ndev,
            "gather_bytes": gather,
            "maxerr": err,
        },
    }


def run(quick: bool = False):
    out = []
    LAST_RESULTS.clear()
    rng = np.random.RandomState(0)
    n, d = (256, 128) if quick else (1024, 256)

    x = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    t = time_fn(jax.jit(ref.kfac_factor_ref), x)
    err = float(jnp.max(jnp.abs(
        ops.kfac_factor(x, bm=64, bn=64, bk=128, interpret=True)
        - ref.kfac_factor_ref(x))))
    LAST_RESULTS["kernel.kfac_factor_syrk"] = {"us": t, "maxerr": err}
    out.append(row("kernel.kfac_factor_syrk", t, f"maxerr={err:.2e}"))

    nb, b, m = (2, 64, 64) if quick else (4, 128, 128)
    binv = jnp.asarray(rng.randn(nb, b, b), jnp.float32)
    w = jnp.asarray(rng.randn(nb, b, m), jnp.float32)
    t = time_fn(jax.jit(ref.block_precond_ref), binv, w)
    err = float(jnp.max(jnp.abs(
        ops.kfac_block_precond(binv, w, bm=32, bn=32, bk=32, interpret=True)
        - ref.block_precond_ref(binv, w))))
    LAST_RESULTS["kernel.kfac_block_precond"] = {"us": t, "maxerr": err}
    out.append(row("kernel.kfac_block_precond", t, f"maxerr={err:.2e}"))

    bh, s, hd, win = (2, 64, 32, 16) if quick else (4, 128, 64, 32)
    q = jnp.asarray(rng.randn(bh, s, hd), jnp.float32)
    k = jnp.asarray(rng.randn(bh, s, hd), jnp.float32)
    v = jnp.asarray(rng.randn(bh, s, hd), jnp.float32)
    t = time_fn(jax.jit(lambda q, k, v: ref.swa_attention_ref(
        q, k, v, window=win)), q, k, v)
    err = float(jnp.max(jnp.abs(
        ops.swa_attention(q, k, v, window=win, bq=32, bk=32, interpret=True)
        - ref.swa_attention_ref(q, k, v, window=win))))
    LAST_RESULTS["kernel.swa_attention"] = {"us": t, "maxerr": err}
    out.append(row("kernel.swa_attention", t, f"maxerr={err:.2e}"))

    # ---- fp8 pack/unpack: ref-vs-pallas A/B + stale-memory ratio ----
    from repro.core.stale import stat_payload_bytes
    from repro.kernels import dispatch

    nbq, bq = (2, 48) if quick else (4, 96)
    fq = rng.randn(nbq, bq, bq).astype(np.float32)
    fq = jnp.asarray(fq + np.swapaxes(fq, -1, -2))
    pack_ref = jax.jit(lambda f: dispatch.fp8_pack(f, backend="ref"))
    t = time_fn(pack_ref, fq)
    pay_r, sc_r = pack_ref(fq)
    pay_p, sc_p = dispatch.fp8_pack(fq, backend="pallas")
    err = max(float(jnp.max(jnp.abs(pay_r.astype(jnp.float32)
                                    - pay_p.astype(jnp.float32)))),
              float(jnp.max(jnp.abs(sc_r - sc_p))))
    LAST_RESULTS["kernel.fp8_pack"] = {"us": t, "maxerr": err}
    out.append(row("kernel.fp8_pack", t, f"maxerr={err:.2e}"))

    unpack_ref = jax.jit(lambda p, s: dispatch.fp8_unpack(p, s, bq,
                                                          backend="ref"))
    t = time_fn(unpack_ref, pay_r, sc_r)
    err = float(jnp.max(jnp.abs(
        unpack_ref(pay_r, sc_r)
        - dispatch.fp8_unpack(pay_p, sc_p, bq, backend="pallas"))))
    LAST_RESULTS["kernel.fp8_unpack"] = {"us": t, "maxerr": err}
    out.append(row("kernel.fp8_unpack", t, f"maxerr={err:.2e}"))

    # resident/communicated bytes of the fp8 payload vs dense fp32 for one
    # sym-packed factor of this shape (paper §4.3 + §5.2 on top of packing)
    fp8_b = stat_payload_bytes(fq.shape, "fp8_e4m3")
    f32_b = int(np.prod(fq.shape)) * 4
    LAST_RESULTS["stale_memory.fp8_over_fp32"] = {
        "ratio": fp8_b / f32_b, "fp8_bytes": fp8_b, "fp32_dense_bytes": f32_b,
    }
    out.append(row("stale_memory.fp8_over_fp32", 0.0,
                   f"ratio={fp8_b / f32_b:.3f}"))

    # ---- Stage-4 inversion A/B: ref eigh vs Pallas Newton-Schulz ----
    di = _bench_damped_inverse(quick)
    for name, rec in di.items():
        LAST_RESULTS[f"damped_inverse.{name}"] = rec
        extra = (f"maxerr={rec['maxerr']:.2e}" if "maxerr" in rec
                 else f"flops={rec['flops']:.3g}")
        out.append(row(f"damped_inverse.{name}", rec["us"], extra))
    LAST_RESULTS["damped_inverse.ns_over_eigh"] = {
        "us_ratio": di["newton_schulz"]["us"] / di["eigh"]["us"],
        "ns_gemm_flops": di["newton_schulz"]["flops"],
    }
    out.append(row("damped_inverse.ns_over_eigh", 0.0,
                   f"us_ratio={di['newton_schulz']['us'] / di['eigh']['us']:.2f}"))

    # ---- Stage-4 distribution A/B: replicated vs sharded refresh ----
    s4 = _bench_stage4(quick)
    for name, rec in s4.items():
        LAST_RESULTS[name] = rec
        if "us_ratio" in rec:
            extra = f"us_ratio={rec['us_ratio']:.3f}"
        elif "maxerr_vs_replicated" in rec:
            extra = f"maxerr={rec['maxerr_vs_replicated']:.2e}"
        else:
            extra = f"devices={rec['devices']}"
        out.append(row(name, rec.get("us", 0.0), extra))

    # ---- Stage-3 comm strategy A/B: dense vs ring vs ring_fp8 ----
    cm = _bench_comm(quick)
    for name, rec in cm.items():
        LAST_RESULTS[name] = rec
        if "ratio" in rec:
            extra = f"ratio={rec['ratio']:.3f}"
        elif "wire_ratio" in rec:
            extra = (f"wire_ratio={rec['wire_ratio']:.3f} "
                     f"maxerr={rec['maxerr']:.2e}")
        else:
            extra = f"wire_bytes={rec['wire_bytes']}"
        out.append(row(name, rec.get("us", 0.0), extra))

    # ---- attention backward A/B: recompute-through-ref VJP vs fused ----
    ab = _bench_attn_bwd(quick)
    for name, rec in ab.items():
        LAST_RESULTS[f"attn_bwd.{name}"] = rec
        out.append(row(f"attn_bwd.{name}", rec["us"],
                       f"bwd_flops={rec['bwd_flops']:.3g}"))
    ratio = (ab["fused"]["bwd_flops"] / ab["recompute"]["bwd_flops"]
             if ab["recompute"]["bwd_flops"] else float("nan"))
    LAST_RESULTS["attn_bwd.fused_over_recompute"] = {
        "flops_ratio": ratio,
        "us_ratio": ab["fused"]["us"] / ab["recompute"]["us"],
    }
    out.append(row("attn_bwd.fused_over_recompute", 0.0,
                   f"flops_ratio={ratio:.3f}"))

    # ---- serving decode A/B: dense-cache walk vs ring flash decode ----
    sv = _bench_serve(quick)
    for name, rec in sv.items():
        LAST_RESULTS[name] = rec
        if "us_ratio" in rec:
            extra = f"us_ratio={rec['us_ratio']:.3f}"
        elif "ratio" in rec:
            extra = f"ratio={rec['ratio']:.3f}"
        else:
            extra = (f"max_len={rec['max_len']}" if "max_len" in rec
                     else f"window={rec['window']}")
        out.append(row(name, rec.get("us", 0.0), extra))

    # ---- end-to-end dispatch A/B: full train_step per backend ----
    for backend in ("ref", "pallas"):
        t, loss = _bench_train_step(backend, quick)
        LAST_RESULTS[f"train_step.{backend}"] = {"us": t, "loss": loss}
        out.append(row(f"train_step.{backend}", t, f"loss={loss:.4f}"))
    r = LAST_RESULTS["train_step.ref"]["us"]
    p = LAST_RESULTS["train_step.pallas"]["us"]
    LAST_RESULTS["train_step.pallas_over_ref"] = {"ratio": p / r}
    out.append(row("train_step.pallas_over_ref", 0.0, f"ratio={p / r:.2f}"))

    # ---- telemetry cost A/B: metrics stream enabled vs disabled ----
    ob = _bench_obs(quick)
    LAST_RESULTS["obs.loop_disabled"] = {"us": ob["disabled_us"]}
    LAST_RESULTS["obs.loop_enabled"] = {"us": ob["enabled_us"]}
    LAST_RESULTS["obs.enabled_over_disabled"] = {"ratio": ob["ratio"]}
    out.append(row("obs.loop_disabled", ob["disabled_us"],
                   f"steps={ob['steps']}"))
    out.append(row("obs.loop_enabled", ob["enabled_us"],
                   f"steps={ob['steps']}"))
    out.append(row("obs.enabled_over_disabled", 0.0,
                   f"ratio={ob['ratio']:.3f}"))

    # ---- Stage-4 overlap A/B: chunked pipeline vs inline refresh ----
    # LAST in the sequence: this subprocess runs minutes of full train
    # steps, and the rows measured after it would inherit its thermal /
    # memory shadow (observed inflating comm.* by ~40%)
    ov = _bench_overlap(quick)
    for name, rec in ov.items():
        LAST_RESULTS[name] = rec
        if "us_ratio" in rec:
            extra = f"us_ratio={rec['us_ratio']:.3f} chunks={rec['chunks']}"
        elif "chunks" in rec:
            extra = f"chunks={rec['chunks']}"
        else:
            extra = f"devices={rec['devices']}"
        out.append(row(name, rec.get("us", 0.0), extra))
    return out


if __name__ == "__main__":
    import sys
    if "--comm-json" in sys.argv:
        # subprocess entry for _bench_comm: emit the comm A/B dict as the
        # last stdout line (the parent parses it)
        import json
        print(json.dumps(_bench_comm_local(quick="--quick" in sys.argv)))
    elif "--stage4-json" in sys.argv:
        import json
        print(json.dumps(_bench_stage4_local(quick="--quick" in sys.argv)))
    elif "--overlap-json" in sys.argv:
        import json
        print(json.dumps(_bench_overlap_local(quick="--quick" in sys.argv)))
    else:
        for r in run():
            print(r)

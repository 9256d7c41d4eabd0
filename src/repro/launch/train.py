"""Train / serve step builders: microbatch accumulation + SP-NGD update.

``make_train_step(model, opt, accum)`` returns a pure jittable function

    train_step(params, opt_state, batch, flags, lam, lr, mom)
        -> (params, opt_state, metrics)

With ``accum > 1`` the global batch is split into microbatches scanned
sequentially; gradients average and raw factor sums add — the paper's own
statistics-accumulation method for extreme batch sizes (§7.1). The G-type
raw sums are rescaled by 1/accum^2 so the tokens-as-samples normalization
stays exact (each microbatch's dL/ds carries a 1/n_micro, not 1/n_total).

Both single-program builders hand the optimizer the model's
``site_rows(batch)``: the token ids of the whole batch, microbatches
included, so an embedding's gradient is preconditioned at those rows
alone. The shard_map builders pass none: their gradient sums the rows of
every data shard, which a shard's own ids do not cover.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.ngd import SPNGD
from repro.obs.tracing import (HOST_DISPATCH, HOST_FLAGS, HOST_SIMS,
                               HOST_STEP, STAGE_CAPTURE, STAGE_FWD_BWD,
                               STAGE_REDUCE, STAGE_STATS, Span)


def _check_accum_capture(opt: SPNGD, accum: int) -> None:
    """Fused wire-format capture (FactorSpec.wire_fmt) emits fp8 payloads
    whose microbatch sums are NOT representable (fp8 has no add); refuse
    the scan-accumulation schedules up front instead of silently adding
    quantized payloads."""
    if accum <= 1:
        return
    from repro import quant
    template = jax.eval_shape(opt.fstats_fn)
    wired = [f"{fam}.{k}" for fam, stats in template.items()
             for k, leaf in stats.items() if quant.is_wire(leaf)]
    if wired:
        raise ValueError(
            f"accum={accum} cannot accumulate wire-format statistics "
            f"({', '.join(sorted(wired))}): fp8 payloads do not add across "
            "microbatches. Use accum=1 with fused capture, or dense "
            "capture (FactorSpec.wire_fmt='') with accumulation.")


def make_train_step(model, opt: SPNGD, accum: int = 1) -> Callable:
    _check_accum_capture(opt, accum)

    def train_step(params, opt_state, batch, flags, lam, lr, mom):
        with jax.named_scope(STAGE_STATS):
            counts = model.site_counts(batch)      # full-batch counts
        rows = model.site_rows(batch)

        if accum == 1:
            loss, aux, grads, raw = opt.grads_and_raw(params, batch)
            loss_mean = loss
        else:
            with jax.named_scope(STAGE_CAPTURE):
                micro = jax.tree.map(
                    lambda x: x.reshape((accum, x.shape[0] // accum)
                                        + x.shape[1:]), batch)
                mb0 = jax.tree.map(lambda x: x[0], micro)
                g_shape = jax.eval_shape(opt.grads_and_raw, params, mb0)
                zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                     (g_shape[2], g_shape[3]))

                def body(carry, mb):
                    g_acc, r_acc, l_acc = carry
                    loss, aux, g, r = opt.grads_and_raw(params, mb)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    r_acc = jax.tree.map(jnp.add, r_acc, r)
                    return (g_acc, r_acc, l_acc + loss), None

                (grads, raw, loss_sum), _ = jax.lax.scan(
                    body, (zeros[0], zeros[1], jnp.zeros((), jnp.float32)),
                    micro)
                grads = jax.tree.map(lambda g: g / accum, grads)
                # G-type raw sums: undo the microbatch mean-loss scaling
                raw = {fam: {k: (v if k == "a" else v / (accum * accum))
                             for k, v in stats.items()}
                       for fam, stats in raw.items()}
                loss_mean = loss_sum / accum
                aux = {}

        return opt.apply_update(params, opt_state, grads, raw, counts,
                                flags, lam, lr, mom, loss_mean, aux, rows)

    return train_step


def make_fast_step(model, opt: SPNGD, accum: int = 1) -> Callable:
    """No-capture step (all statistics within their refresh interval)."""
    def fast_step(params, opt_state, batch, lam, lr, mom):
        rows = model.site_rows(batch)
        if accum == 1:
            return opt.step_fast(params, opt_state, batch, lam, lr, mom,
                                 rows)
        with jax.named_scope(STAGE_FWD_BWD):
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)

            def body(carry, mb):
                g_acc, l_acc = carry
                (loss, aux), g = jax.value_and_grad(
                    opt.loss_fn, has_aux=True)(params, None, mb)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + loss), None

            zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                                 params)
            (grads, loss_sum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / accum, grads)
        opt_state, curv, extra = opt.fast_curv(opt_state, lam)
        return opt._finish(params, opt_state, grads, curv,
                           lam, lr, mom, loss_sum / accum, {}, {},
                           extra_metrics=extra, rows=rows)

    return fast_step


def make_shardmap_train_step(model, opt: SPNGD, mesh, accum: int = 1,
                             counts_fn=None,
                             manual_axes: str = "auto",
                             comm=None) -> Callable:
    """The paper's Algorithm 3 with EXPLICIT collectives (shard_map over the
    data axes; the model/TP axis stays compiler-managed):

      Stage 1-2: forward/backward on the LOCAL batch shard — gradients and
                 raw factor sums accumulate across microbatches with NO
                 cross-device traffic (GSPMD-auto inserts per-layer
                 all-reduces inside the backward scan; doing it manually
                 defers everything to one sync point).
      Stage 3:   one ``psum`` for the gradients + one reduce-scatter per
                 factor family, scattering the layer axis across the data
                 axes — the ReduceScatterV of the paper. The collective is
                 owned by :class:`repro.comm.FactorReducer`; ``comm``
                 (a :class:`repro.comm.CommConfig`) selects the strategy:
                 dense psum_scatter (default, bit-compatible), ring
                 reduce-scatter over sym-packed triangles, or the fp8-wire
                 ring.
      Stage 4:   inversion + preconditioning run on layer-sharded factors
                 (the sharding hook keeps them scattered).
      Stage 5:   the updated weights' all-gather is GSPMD's job (weights are
                 replicated over data, so the preconditioned update is
                 gathered exactly once).
    """
    from jax.sharding import PartitionSpec as P

    from repro.comm import FactorReducer, Stage4Inverter
    _check_accum_capture(opt, accum)
    reducer = FactorReducer(mesh, manual_axes=manual_axes, comm=comm,
                            template=jax.eval_shape(opt.fstats_fn),
                            sym_fn=opt.sym_stat)
    dp, ndev = reducer.dp, reducer.ndev
    if opt.cfg.inverse_sharding:
        # Stage-4 distribution: the refresh's full-kind inverses run shard-
        # locally over THIS reducer's chunk layout and all-gather. Attached
        # here (not in the optimizer) because ownership is the reducer's.
        opt.set_stage4(Stage4Inverter(reducer, method=opt.cfg.inverse_method,
                                      backend=opt.cfg.backend,
                                      ns_iters=opt.cfg.ns_iters,
                                      ns_tol=opt.cfg.ns_tol))

    def inner(params, batch):
        if accum == 1:
            loss, aux, grads, raw = opt.grads_and_raw(params, batch)
            loss_sum = loss
        else:
            with jax.named_scope(STAGE_CAPTURE):
                micro = jax.tree.map(
                    lambda x: x.reshape((accum, x.shape[0] // accum)
                                        + x.shape[1:]), batch)
                mb0 = jax.tree.map(lambda x: x[0], micro)
                g_shape = jax.eval_shape(opt.grads_and_raw, params, mb0)
                zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                     (g_shape[2], g_shape[3]))

                def body(carry, mb):
                    g_acc, r_acc, l_acc = carry
                    loss, aux, g, r = opt.grads_and_raw(params, mb)
                    return (jax.tree.map(jnp.add, g_acc, g),
                            jax.tree.map(jnp.add, r_acc, r),
                            l_acc + loss), None

                (grads, raw, loss_sum), _ = jax.lax.scan(
                    body, (zeros[0], zeros[1], jnp.zeros((), jnp.float32)),
                    micro)

        # ---- Stage 3: explicit collectives, once per step ----
        with jax.named_scope(STAGE_REDUCE):
            loss = reducer.psum(loss_sum) / (ndev * accum)
            grads = jax.tree.map(lambda g: reducer.psum(g) / (ndev * accum),
                                 grads)
            g_scale = 1.0 / (accum * accum * ndev * ndev)
            # undo local-mean-loss scaling BEFORE the reduce (the fp8 wire
            # quantizes what actually travels). Fused wire-format capture
            # already quantized the payload — rescale its per-block scales
            # instead, which is mathematically exact.
            from repro import quant

            def _rescale_g(v):
                if quant.is_wire(v):
                    return {"payload": v["payload"],
                            "scale": v["scale"] * g_scale}
                return v * g_scale

            raw = {fam: {k: (v if k == "a" else _rescale_g(v))
                         for k, v in stats.items()}
                   for fam, stats in raw.items()}
        return loss, grads, reducer.reduce(raw)

    def train_step(params, opt_state, batch, flags, lam, lr, mom):
        with jax.named_scope(STAGE_STATS):
            counts = model.site_counts(batch)
        batch_specs = jax.tree.map(
            lambda x: P(dp, *(None,) * (x.ndim - 1)), batch)
        sm = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), batch_specs),
            out_specs=(P(), P(), reducer.out_specs()),
            axis_names=set(dp), check_vma=False)
        loss, grads, raw = sm(params, batch)
        return opt.apply_update(params, opt_state, grads, raw, counts,
                                flags, lam, lr, mom, loss, {})

    train_step.reducer = reducer     # launch layer: ledger + tally access
    return train_step


def make_shardmap_fast_step(model, opt: SPNGD, mesh, accum: int = 1,
                            manual_axes: str = "auto",
                            comm=None) -> Callable:
    """Algorithm 1 fast path under the explicit schedule: no statistic
    refreshes this step — backward + ONE gradient psum + stale-preconditioned
    update. This is the steady-state step whose cost the paper drives down to
    ~SGD. The reducer owns the collective axes here too (no factor traffic,
    so the strategy only picks which axes the gradient psum runs over)."""
    from jax.sharding import PartitionSpec as P

    from repro.comm import FactorReducer
    reducer = FactorReducer(mesh, manual_axes=manual_axes, comm=comm)
    dp, ndev = reducer.dp, reducer.ndev

    def inner(params, batch):
        with jax.named_scope(STAGE_FWD_BWD):
            if accum == 1:
                (loss, aux), grads = jax.value_and_grad(
                    opt.loss_fn, has_aux=True)(params, None, batch)
                loss_sum = loss
            else:
                micro = jax.tree.map(
                    lambda x: x.reshape((accum, x.shape[0] // accum)
                                        + x.shape[1:]), batch)

                def body(carry, mb):
                    g_acc, l_acc = carry
                    (loss, aux), g = jax.value_and_grad(
                        opt.loss_fn, has_aux=True)(params, None, mb)
                    return (jax.tree.map(jnp.add, g_acc, g),
                            l_acc + loss), None

                zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                                     params)
                (grads, loss_sum), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros((), jnp.float32)), micro)
        with jax.named_scope(STAGE_REDUCE):
            loss = reducer.psum(loss_sum) / (ndev * accum)
            grads = jax.tree.map(lambda g: reducer.psum(g) / (ndev * accum),
                                 grads)
        return loss, grads

    def fast_step(params, opt_state, batch, lam, lr, mom):
        batch_specs = jax.tree.map(
            lambda x: P(dp, *(None,) * (x.ndim - 1)), batch)
        sm = jax.shard_map(inner, mesh=mesh, in_specs=(P(), batch_specs),
                           out_specs=(P(), P()), axis_names=set(dp),
                           check_vma=False)
        loss, grads = sm(params, batch)
        # fast_curv drains one refresh-pipeline chunk (refresh_chunks > 1)
        # or performs the plain double-buffer activation. The drain runs
        # OUTSIDE the manual region: Stage4Inverter opens its own shard_map
        # for the chunk's shard-local inverses + gathers, exactly as the
        # inline refresh path does.
        opt_state, curv, extra = opt.fast_curv(opt_state, lam)
        return opt._finish(params, opt_state, grads, curv,
                           lam, lr, mom, loss, {}, {}, extra_metrics=extra)

    fast_step.reducer = reducer
    return fast_step


def make_serve_step(model) -> Callable:
    """Single-token decode against a persistent cache."""
    def serve_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)
    return serve_step


def make_prefill_step(model) -> Callable:
    def prefill_step(params, batch):
        logits, _ = model.forward(params, batch)
        return logits
    return prefill_step


# ---------------------------------------------------------------------------
# one training step, as the host drives it
# ---------------------------------------------------------------------------

def take_step(step_j, fast_j, ctrl, t, params, state, batch, lam, lr, mom):
    """Step ``t`` of the training loop: the controller's flags pick the
    program; a capture step (``step_j``, flags on the device) reads back
    each statistic's two similarities for the controller, a fast step
    (``fast_j``) passes none. Returns ``(params, state, metrics, flags)``.

    Each host phase runs under a :class:`~repro.obs.tracing.Span`, so a
    profiler trace names what the host does while the device idles:
    ``spngd.host.step`` (the whole step; ``kind`` capture or fast),
    ``spngd.host.flags`` (``n`` flags moved to the device),
    ``spngd.host.dispatch`` (the call of the jitted program) and
    ``spngd.host.sims`` (``n`` scalars read back: the host waits for the
    capture here); ``IntervalController.update`` opens
    ``spngd.host.controller`` itself."""
    with Span(HOST_STEP, step_num=t) as step:
        with Span(HOST_FLAGS) as moved:
            flags = ctrl.flags(t)
            capture = any(flags.values())
            jflags = ({k: jnp.asarray(v) for k, v in flags.items()}
                      if capture else {})
            moved.set(n=len(jflags))
        step.set(kind="capture" if capture else "fast")
        if capture:
            with Span(HOST_DISPATCH):
                params, state, m = step_j(params, state, batch, jflags,
                                          lam, lr, mom)
            with Span(HOST_SIMS, n=2 * len(m["sims"])):
                sims = {k: (float(v[0]), float(v[1]))
                        for k, v in m["sims"].items()}
            ctrl.update(t, flags, sims)
        else:
            with Span(HOST_DISPATCH):
                params, state, m = fast_j(params, state, batch, lam, lr, mom)
            ctrl.update(t, flags, {})
    return params, state, m, flags


# ---------------------------------------------------------------------------
# overhead-accounting probe (repro.obs; make_report.py's decomposition input)
# ---------------------------------------------------------------------------

def _probe_time(fn, *args, iters: int = 3, donated: tuple = ()) -> float:
    """Median wall-µs of ``fn(*args)`` after one compile+warm call. The
    arguments at positions ``donated`` are consumed by each call (buffer
    donation), so every call gets its own copy, made outside the timed
    window."""
    import time as _time

    def fresh():
        return [jax.tree.map(jnp.copy, a) if i in donated else a
                for i, a in enumerate(args)]

    jax.block_until_ready(fn(*fresh()))
    ts = []
    for _ in range(iters):
        call_args = jax.block_until_ready(fresh())
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*call_args))
        ts.append((_time.perf_counter() - t0) * 1e6)
    return sorted(ts)[len(ts) // 2]


def _overhead_probe(opt, step_j, fast_j, params, state, batch, args,
                    lr_fn, log) -> None:
    """Time the step's stage-isolated building blocks and emit one ``probe``
    event. The monolithic jitted step cannot be decomposed from its own
    wall time, so the probe measures four nested programs — forward/backward
    only, +Stage-2 capture, the fast step, the all-flags refresh step — plus
    a per-factor Stage-4 inversion stand-in (the dryrun ``stage4_report``
    recipe). ``make_report.py`` combines these with the metrics stream's
    measured refresh frequency into the paper's overhead-decomposition
    table (fraction of step time in Stage 2/3/4 vs forward/backward)."""
    import numpy as np

    from repro.core.ngd import _dense_leaf_shape
    from repro.kernels import dispatch

    lr0 = lr_fn(0)
    mom0 = 0.9 * lr0 / args.lr
    lam = args.damping

    fwd_bwd_j = jax.jit(lambda p, b: jax.value_and_grad(
        opt.loss_fn, has_aux=True)(p, None, b))
    capture_j = jax.jit(lambda p, b: opt.grads_and_raw(p, b))
    all_on = {k: jnp.asarray(True) for k in opt.stat_names()}

    fwd_bwd_us = _probe_time(fwd_bwd_j, params, batch)
    capture_us = _probe_time(capture_j, params, batch)
    # the step programs donate params and optimizer state
    fast_us = _probe_time(fast_j, params, state, batch, lam, lr0, mom0,
                          donated=(0, 1))
    refresh_us = _probe_time(step_j, params, state, batch, all_on,
                             lam, lr0, mom0, donated=(0, 1))

    # Stage-4 inversion in isolation: one damped_inverse per full-kind
    # factor on an SPD stand-in shaped like the real statistic
    rng = np.random.RandomState(0)
    inv_per_stat = {}
    for fam, stats in jax.eval_shape(opt.fstats_fn).items():
        for key, leaf in stats.items():
            if key not in ("a", "g") or not opt.sym_stat(fam, key):
                continue
            shape = _dense_leaf_shape(leaf)
            b = shape[-1]
            m = rng.randn(*shape[:-1], b).astype(np.float32)
            spd = jnp.asarray(m @ np.swapaxes(m, -1, -2) / b
                              + 0.1 * np.eye(b, dtype=np.float32))
            fn = jax.jit(lambda s: dispatch.damped_inverse(
                s, jnp.asarray(lam, jnp.float32),
                method=opt.cfg.inverse_method, ns_iters=opt.cfg.ns_iters,
                ns_tol=opt.cfg.ns_tol, backend=opt.cfg.backend))
            inv_per_stat[f"{fam}.{key}"] = _probe_time(fn, spd, iters=1)
    log.emit("probe", fwd_bwd_us=fwd_bwd_us, capture_us=capture_us,
             fast_us=fast_us, refresh_us=refresh_us,
             inverse_us=sum(inv_per_stat.values()),
             inverse_us_per_stat=inv_per_stat)


# ---------------------------------------------------------------------------
# CLI launcher: train any --arch (reduced) on the synthetic LM task
# ---------------------------------------------------------------------------

def build_parser():
    """The trainer's command line; :func:`run` takes its parsed settings."""
    import argparse

    ap = argparse.ArgumentParser(
        description="SP-NGD trainer (reduced configs on CPU; the full "
                    "configs are exercised via repro.launch.dryrun)")
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=2e-2)
    ap.add_argument("--damping", type=float, default=2.5e-4)
    ap.add_argument("--backend", default="auto",
                    choices=["ref", "pallas", "auto"],
                    help="kernel backend for the SP-NGD hot paths "
                         "(repro.kernels.dispatch); pallas trains attention "
                         "through the fused dq/dk/dv backward kernels "
                         "(residual-saving forward, no recompute pass)")
    from repro.quant import FACTOR_DTYPES
    ap.add_argument("--factor-dtype", default="f32",
                    choices=sorted(FACTOR_DTYPES),
                    help="storage dtype for the X_-1/X_-2 factor history "
                         "and the statistics payload ledger; fp8 variants "
                         "store sym-packed payloads + per-block scales "
                         "(repro.quant) and dequantize on read")
    ap.add_argument("--inverse-method", default="eigh",
                    choices=["eigh", "cholesky", "newton_schulz"],
                    help="Stage-4 factor inversion: direct factorization "
                         "(eigh/cholesky) or the matmul-only Newton-Schulz "
                         "iteration (Pallas kernel under --backend pallas; "
                         "blocks that fail to contract re-solve via eigh)")
    from repro import comm as comm_lib
    ap.add_argument("--comm-strategy", default="dense",
                    choices=comm_lib.STRATEGIES,
                    help="Stage-3 factor reduce strategy (repro.comm): "
                         "dense psum_scatter (bit-compatible default), ring "
                         "reduce-scatter over sym-packed triangles, "
                         "ring_fp8 (fp8 wire payloads + per-block scales, "
                         "f32 accumulation per hop), hier (intra-host f32 "
                         "psum_scatter + inter-host fp8 ring), or fused "
                         "(wire-format payloads emitted by the SYRK "
                         "epilogue). This single-process CLI runs the jit "
                         "schedule (no collectives) — the flag here MODELS "
                         "the wire ledger; the collective itself runs under "
                         "make_shardmap_train_step "
                         "(repro.launch.dryrun --schedule shardmap)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=sorted(comm_lib.WIRE_DTYPES),
                    help="collective wire dtype; defaults to f32 for "
                         "dense/ring and fp8_e4m3 for ring_fp8/hier/fused")
    ap.add_argument("--devices-per-host", type=int, default=None,
                    help="host-topology model for the hier strategy: group "
                         "size of the full-precision intra-host level "
                         "(default: jax.local_device_count())")
    ap.add_argument("--inverse-sharding", action="store_true",
                    help="Stage-4 distribution: invert only the local "
                         "factor shard (FactorReducer chunk ownership) and "
                         "all-gather preconditioners as sym-packed f32 "
                         "triangles. Implies --double-buffer (the pipelined "
                         "mode the paper describes). This single-process "
                         "CLI runs the jit schedule, so the flag here "
                         "MODELS the gather ledger; the sharded inversion "
                         "itself runs under make_shardmap_train_step "
                         "(repro.launch.dryrun --schedule shardmap)")
    ap.add_argument("--double-buffer", action="store_true",
                    help="pipeline refreshes: inverses computed at step t "
                         "activate at t+1 while t consumes the previous "
                         "buffer (Algorithm 2 still governs staleness)")
    ap.add_argument("--refresh-chunks", type=int, default=1,
                    help="chunked refresh pipeline (repro.core.pipeline): "
                         "K>1 turns each refresh into a capture step "
                         "(Stage-2/3 + similarities only) followed by K "
                         "drain chunks of Stage-4 inversions+gathers, one "
                         "fused into each subsequent fast step, activated "
                         "atomically K+1 steps after the capture. Implies "
                         "--double-buffer and floors the refresh interval "
                         "at K+1 so a drain always completes. 1 = inline "
                         "refresh (default)")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (non-reduced) architecture")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="write the per-step JSONL event stream here "
                         "(repro.obs.MetricsLogger): loss/lr/norms, refresh "
                         "decisions, drained comm-ledger bytes, NS/eigh "
                         "inversion tallies, step-time EMA + p50/p99. "
                         "Console text is unchanged (and mirrored into the "
                         "stream); disabled = zero-cost no-op")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of whole refresh "
                         "cycles into DIR, from the first capture step "
                         "after the first refresh has activated (both step "
                         "programs compiled) to a capture boundary. Device "
                         "scopes spngd.* and repro.kernels.*[backend] name "
                         "the ops; host spans spngd.host.* name each "
                         "step's host phases")
    ap.add_argument("--profile-steps", type=int, default=3,
                    help="the --profile-dir window holds at least this "
                         "many steps and ends on the next capture step")
    ap.add_argument("--no-overhead-probe", action="store_true",
                    help="skip the stage-isolated timing probe that "
                         "metrics-enabled runs emit for make_report.py's "
                         "overhead-accounting table")
    return ap


def run(cfg, args, *, label: str, on_step: Optional[Callable] = None):
    """Train ``cfg`` on the synthetic LM task with the settings ``args``
    (:func:`build_parser`'s namespace; ``args.arch``/``args.full_config``
    are not read — ``cfg`` is the architecture and ``label`` names it on
    the console). Both step programs donate params and optimizer state.

    ``on_step(record)``, when given, is called after every step with
    ``{"step", "program", "loss", "grad_norm", "update_norm", "dt"}``
    (``program`` is ``"train_step"`` or ``"fast_step"``; ``dt`` waits for
    the step's loss). Returns ``{"params", "state", "opt", "programs",
    "batch", "precond_rows"}``: the final parameters and optimizer state,
    the optimizer, the two jitted step programs, the last batch, and
    ``{family: (rows, d_in)}`` of each embedding family's preconditioning
    (:meth:`~repro.core.ngd.SPNGD.precond_rows`)."""
    import dataclasses

    from repro import comm as comm_lib
    from repro.core.ngd import NGDConfig, SPNGD
    from repro.core.stale import IntervalController
    from repro.data.synthetic import token_batches
    from repro.models.transformer import DecoderLM
    from repro.obs import (STAGE_CHUNK, MetricsLogger, ProfileCapture,
                           inverse_tally)
    from repro.optim.schedules import polynomial_decay
    from repro.quant import FACTOR_DTYPES

    log = MetricsLogger(args.metrics_jsonl)
    cfg = dataclasses.replace(cfg, backend=args.backend)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    log.console(f"{label}, {n / 1e6:.1f}M params")

    inverse_sharding = args.inverse_sharding
    refresh_chunks = max(1, args.refresh_chunks)
    double_buffer = (args.double_buffer or inverse_sharding
                     or refresh_chunks > 1)
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts,
                NGDConfig(damping=args.damping, backend=args.backend,
                          inverse_method=args.inverse_method,
                          factor_dtype=FACTOR_DTYPES[args.factor_dtype],
                          inverse_sharding=inverse_sharding,
                          double_buffer=double_buffer,
                          refresh_chunks=refresh_chunks,
                          # metrics runs surface per-block Stage-4
                          # diagnostics; default runs keep the seed tree.
                          # Capture steps run no inversions, so there is
                          # nothing to report under the chunked pipeline
                          inverse_info=log.enabled and refresh_chunks == 1))
    state = opt.init(params)
    comm_cfg = comm_lib.make_comm_config(args.comm_strategy, args.wire_dtype,
                                         backend=args.backend,
                                         devices_per_host=args.devices_per_host)
    ctrl = IntervalController(opt.stat_names(), alpha=0.1,
                              # a drain takes K chunk steps + the flip:
                              # never capture again before it finishes
                              min_interval=(refresh_chunks + 1
                                            if refresh_chunks > 1 else 1),
                              bytes_per_stat=opt.stat_bytes(),
                              wire_bytes_per_stat=opt.wire_bytes(comm_cfg),
                              wire_level_bytes_per_stat=opt.wire_level_bytes(
                                  comm_cfg),
                              gather_bytes_per_stat=(
                                  opt.gather_bytes() if inverse_sharding
                                  else None))
    ctrl.record_comm({"strategy": comm_cfg.strategy,
                      "wire_dtype": comm_cfg.wire_dtype,
                      "inverse_sharding": inverse_sharding,
                      "double_buffer": double_buffer,
                      "refresh_chunks": refresh_chunks})
    data = token_batches(cfg.vocab, args.batch, args.seq, seed=0)
    lr_fn = polynomial_decay(args.lr, 0, args.steps, 4.0)
    # params and optimizer state are consumed by each step: without
    # donation a full-width step holds both the old and the new copies
    step_j = jax.jit(make_train_step(model, opt, accum=args.accum),
                     donate_argnums=(0, 1))
    fast_j = jax.jit(make_fast_step(model, opt, accum=args.accum),
                     donate_argnums=(0, 1))

    log.emit("run_config", arch=cfg.name, full_config=args.full_config,
             n_params=int(n), steps=args.steps, batch=args.batch,
             seq=args.seq, accum=args.accum, lr=args.lr,
             damping=args.damping, backend=args.backend,
             factor_dtype=args.factor_dtype,
             inverse_method=args.inverse_method,
             comm_strategy=comm_cfg.strategy,
             wire_dtype=comm_cfg.wire_dtype,
             inverse_sharding=inverse_sharding,
             double_buffer=double_buffer,
             refresh_chunks=refresh_chunks)
    # the rows each embedding family's preconditioning covers: fixed by
    # the batch's shape, so one report per compiled program
    precond_rows = opt.precond_rows(model.site_rows(
        {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)}))
    for program in ("train_step", "fast_step"):
        log.emit("precond_rows", program=program,
                 families={fam: {"rows": n, "d_in": d, "share": n / d}
                           for fam, (n, d) in precond_rows.items()})
    # per-block-size Stage-4 tallies need each stat's block size, which the
    # on-device info arrays don't carry — read it off the stats template
    block_sizes = {}
    from repro.core.ngd import _dense_leaf_shape
    for fam, stats in jax.eval_shape(opt.fstats_fn).items():
        for key, leaf in stats.items():
            if key in ("a", "g") and opt.sym_stat(fam, key):
                block_sizes[f"{fam}.{key}"] = _dense_leaf_shape(leaf)[-1]
    if log.enabled and not args.no_overhead_probe:
        # dedicated generator: the probe must not advance the training
        # stream (a metrics run sees the same batches as a default run)
        probe_batch = next(token_batches(cfg.vocab, args.batch, args.seq,
                                         seed=1))
        _overhead_probe(opt, step_j, fast_j, params, state, probe_batch,
                        args, lr_fn, log)
    # steps from a capture until its refresh is live
    settle = (refresh_chunks + 1 if refresh_chunks > 1
              else int(double_buffer))
    prof = ProfileCapture(args.profile_dir,
                          lambda t: any(ctrl.flags(t).values()),
                          steps=args.profile_steps, settle=settle)

    import time as _time
    for t in range(1, args.steps + 1):
        batch = next(data)
        lr = lr_fn(t - 1)
        mom = 0.9 * lr / args.lr
        prof.step_start(t)
        t0 = _time.perf_counter()
        params, state, m, flags = take_step(step_j, fast_j, ctrl, t, params,
                                            state, batch, args.damping, lr,
                                            mom)
        if on_step is not None or log.enabled:
            jax.block_until_ready(m["loss"])
            dt = _time.perf_counter() - t0
        if on_step is not None:
            on_step({"step": t,
                     "program": ("train_step" if any(flags.values())
                                 else "fast_step"),
                     "loss": float(m["loss"]),
                     "grad_norm": float(m["grad_norm"]),
                     "update_norm": float(m["update_norm"]), "dt": dt})
        if log.enabled:
            # chunked pipeline: refresh-trigger steps are CAPTUREs (no
            # inversion runs inline), so the stream's "refresh" kind —
            # which make_report amortizes the inline Stage-3/4 costs
            # over — honestly goes to zero occurrences
            trigger = any(flags.values())
            kind = ("capture" if trigger and refresh_chunks > 1
                    else "refresh" if trigger else "fast")
            evt = {"kind": kind,
                   "lr": lr, "mom": mom,
                   "n_refreshed": sum(flags.values()),
                   "n_stats": len(flags),
                   "refreshed": sorted(k for k, v in flags.items() if v),
                   "grad_norm": float(m["grad_norm"]),
                   "update_norm": float(m["update_norm"]),
                   "comm": ctrl.drain()}
            if "refresh_inflight" in m:
                # steps until the in-flight refresh activates: K+1 on the
                # capture, K..1 across the drain, 0 when idle
                infl = int(m["refresh_inflight"])
                evt["refresh_inflight"] = infl
                if kind == "fast" and 0 < infl <= refresh_chunks + 1:
                    # per-chunk span: the step window this chunk (or, at
                    # infl == 1, the activation flip) was fused into
                    idx = refresh_chunks + 1 - infl
                    chunk = (opt.pipeline.chunk_names(idx)
                             if idx < refresh_chunks else [])
                    log.emit("span",
                             name=(f"{STAGE_CHUNK}[{idx}]"
                                   if idx < refresh_chunks
                                   else f"{STAGE_CHUNK}[flip]"),
                             start=t0, dur=dt, depth=0, parent=None,
                             step=t, stats=chunk)
            if "inverse_info" in m:
                evt["inverse"] = inverse_tally(m["inverse_info"],
                                               block_sizes)
            log.log_step(t, loss=float(m["loss"]), dt=dt, **evt)
        prof.step_end(t, m)
        if t % 10 == 0 or t == 1:
            log.console(f"step {t:4d} loss {float(m['loss']):.4f} "
                        f"lr {lr:.4f} "
                        f"refresh {sum(flags.values())}/{len(flags)}")
    prof.stop()
    s = ctrl.summary()
    log.console(f"statistic traffic: {100 * s['reduction_rate']:.1f}% of "
                f"dense; "
                f"modelled wire [{comm_cfg.strategy}/{comm_cfg.wire_dtype}]: "
                f"{s['comm']['total_wire_bytes']} B "
                f"({100 * s['comm']['wire_reduction_rate']:.1f}% of "
                f"refresh-every-step)")
    if inverse_sharding:
        log.console(f"modelled Stage-4 gather (sym-packed f32): "
                    f"{s['comm']['total_gather_bytes']} B")
    log.emit("summary", **ctrl.summary_flat())
    log.close()
    return {"params": params, "state": state, "opt": opt, "batch": batch,
            "programs": {"train_step": step_j, "fast_step": fast_j},
            "precond_rows": precond_rows}


def main():
    from repro.configs import get_config
    from repro.launch.cache import use_compile_cache

    args = build_parser().parse_args()
    use_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.reduced()
    run(cfg, args, label=(f"arch={args.arch} "
                          f"({'full' if args.full_config else 'reduced'})"))


if __name__ == "__main__":
    main()

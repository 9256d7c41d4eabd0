"""Bring-up smoke run of the SP-NGD trainer on TPU, at published widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # data-parallel schedule, 2x2 host

The model is Qwen1.5-4B (``hf:Qwen/Qwen1.5-4B``: d_model 2560, 20 heads
of 128, d_ff 6912, the whole 151936-token vocabulary) with its depth cut
from 40 layers to 4, which stands for one pipeline stage of a 10-chip
deployment. Weights are random from a fixed seed and the data is the
synthetic LM stream (``repro.data.synthetic``).

One chip runs two phases:

* ``train`` — :func:`repro.launch.train.run`, the loop behind
  ``python -m repro.launch.train``, with SP-NGD on ``backend="auto"``
  (Pallas kernels on TPU), the Algorithm-2 interval controller and the
  chunked refresh pipeline (``--refresh-chunks``, see
  :data:`REFRESH_CHUNKS`). Step 1 captures every statistic through
  ``make_train_step``; later steps take ``make_fast_step`` wherever the
  controller says so, draining the refresh's inversions in chunks.
* ``check`` — the same loop for one inline-refresh step at one layer,
  under ``backend="ref"`` and ``backend="auto"``, from identical params,
  state and batch: loss and update norms must agree within
  :data:`TOLERANCE`.

``--four-chips`` runs only the data-parallel schedule:
``make_shardmap_train_step`` (FactorReducer reduce-scatter, sharded
Stage-4 inversion) on a ``(data=4, model=1)`` mesh, against
``make_train_step`` jitted with the batch sharded over the same mesh
(step 1 of both must agree within :data:`FOUR_CHIP_TOLERANCE`), then one
``make_shardmap_fast_step``.

Every line but the last is a report. The last is one JSON object,
``{"ok": true, "device": {...}}``. The script exits non-zero, without that
line, when JAX finds no TPU or any check fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen1_5_4b"
N_LAYERS = 4            # of 40: one stage of a 10-stage pipeline
CHECK_LAYERS = 1        # the ref/auto check: bounds its two extra compiles
# batch x seq from compiled.memory_analysis() of the two 4-layer step
# programs compiled for one v5e (16 GiB): arguments + outputs + temporaries
# - aliased is ~14.2 GB for each at 2 x 1024; 4 x 1024 leaves too little
# headroom for the buffers the compiler does not count
BATCH, SEQ = 2, 1024
STEPS = 8
# Algorithm 2 with alpha = 0.1 refreshes every statistic on every step at
# 2048 tokens per step: a statistic's sampling noise alone puts its
# distance to the last refresh near 1 (embed.a, token counts over a
# 151936-word vocabulary, never settles). The chunked refresh pipeline
# floors the controller's interval at REFRESH_CHUNKS + 1 steps, so the
# fast program runs between refreshes: steps 1, 4, 7 refresh, the rest
# are fast steps that drain the refresh in REFRESH_CHUNKS chunks.
REFRESH_CHUNKS = 2
MIN_FAST_STEPS = 2
# a tenth of the trainer's default: the damped inverses that activate at
# step 4 (damping 2.5e-4) scale the update up ten-fold, and at 2e-2 the
# reduced model's loss triples by step 7
LR = 2e-3
# Stage-4 inversion by Cholesky, which inverts the same damped SPD blocks
# as eigh. On a v5e at these widths the eigh drain took 140-205 s per chunk
# with XLA's Jacobi solver, which alone would exhaust the run's time limit,
# and Newton-Schulz leaves 2-16 blocks per statistic unconverged, which
# re-solve by eigh.
INVERSE_METHOD = "cholesky"

# Relative limits of the two comparisons, set from readings. Sound: both
# paths feed the MXU bf16 and accumulate in f32, but round bf16
# intermediates at different points (the flash kernels round each tile's
# probabilities to bf16), and the error grows with depth. Step 1, ref vs
# pallas, one layer: loss 5.6e-5, grad_norm 3.6e-5, update_norm 3.5e-6 (CPU,
# interpret mode, vocab cut to 8192, batch 2 x seq 256); loss 2.1e-5,
# grad_norm 1.8e-6, update_norm 1.5e-5 (TPU v5e, the check below). Four
# layers: loss 3.8e-4, grad_norm 9.6e-5, update_norm 3.5e-6 (CPU as above,
# seq 64). Planted faults in the pallas path, one layer on the CPU:
# attention output x (1 + 2^-8) reads loss 3.7e-5, grad_norm 5.6e-3,
# update_norm 6.5e-4; attention window cut to S/2 reads loss 2.7e-3,
# grad_norm 0.36, update_norm 2.2e-2. Each limit sits above the sound
# readings at its depth and below the faults; grad_norm is the sharpest. A
# factor kernel that accumulates in bf16 reads only update_norm 2.3e-5 at
# one step: that fault is left to the kernels' own parity tests.
TOLERANCE = {"loss": 2e-4, "grad_norm": 5e-4, "update_norm": 2e-4}
FOUR_CHIP_TOLERANCE = {"loss": 1e-3, "grad_norm": 5e-4, "update_norm": 2e-4}

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def say(*parts) -> None:
    print(*parts, flush=True)


class CompileLog:
    """Backend compiles of this process, by jitted function name: seconds,
    whether the persistent compilation cache supplied the executable, its
    cache key with the hash of each part of the key, and whether a miss was
    written back. JAX logs the parts at DEBUG; two runs that miss on the
    same program differ in one of them."""

    def __init__(self):
        import logging

        import jax
        self.events: list[dict] = []
        self._parts: dict[str, str] = {}
        self._lookup: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        handler = logging.Handler(logging.DEBUG)
        handler.emit = self._on_log
        for name in ("jax._src.cache_key", "jax._src.compiler",
                     "jax._src.compilation_cache"):
            log = logging.getLogger(name)
            log.setLevel(logging.DEBUG)
            log.addHandler(handler)

    def _on_log(self, rec) -> None:
        import logging
        msg = str(rec.msg)
        if msg.startswith("get_cache_key hash of serialized"):
            self._parts[rec.args[0]] = rec.args[1][:12]
        elif msg.startswith(("Persistent compilation cache hit",
                             "PERSISTENT COMPILATION CACHE MISS")):
            self._lookup = {"cache": ("hit" if msg.startswith("Persistent")
                                      else "miss"),
                            "key": rec.args[1][-16:],
                            "parts": dict(self._parts)}
        elif msg.startswith(("Writing", "Not writing")):
            self._lookup["written"] = rec.getMessage()[:160]
        elif rec.levelno >= logging.WARNING:
            print(rec.getMessage(), file=sys.stderr, flush=True)

    def _on_time(self, event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append({"fun": kw.get("fun_name", "?"),
                                "seconds": seconds, **self._lookup})
            self._lookup = {}

    def report(self, label: str, fun: str) -> None:
        """Say, and forget, the compiles of ``fun`` logged so far."""
        mine = [e for e in self.events if e["fun"] == f"jit({fun})"]
        self.events = [e for e in self.events if e["fun"] != f"jit({fun})"]
        for e in mine:
            parts = " ".join(f"{k.replace(' ', '_')}={v}"
                             for k, v in e.get("parts", {}).items())
            say(f"{label} {fun}: compile {e['seconds']:.1f} s, "
                f"persistent cache {e.get('cache', 'off')}, key "
                f"...{e.get('key', '-')} [{parts}]"
                + (f"; {e['written']}" if "written" in e else ""))


def settings(backend: str, steps: int, n_layers: int,
             refresh_chunks: int = 1):
    """The trainer CLI's settings for one run, and the model config."""
    from repro.configs import get_config
    from repro.launch.train import build_parser
    args = build_parser().parse_args(
        ["--arch", ARCH, "--backend", backend, "--steps", str(steps),
         "--batch", str(BATCH), "--seq", str(SEQ), "--lr", str(LR),
         "--inverse-method", INVERSE_METHOD,
         "--refresh-chunks", str(refresh_chunks)])
    cfg = dataclasses.replace(get_config(ARCH), n_layers=n_layers)
    return cfg, args


def finite_record(rec: dict) -> dict:
    bad = [k for k in ("loss", "grad_norm", "update_norm")
           if not math.isfinite(rec[k])]
    if bad:
        raise RuntimeError(f"step {rec['step']}: non-finite {bad}: {rec}")
    return rec


def compare(name: str, a: dict, b: dict, tol: dict) -> None:
    """Raise unless each metric of ``a`` and ``b`` agrees within ``tol``."""
    for k, rtol in tol.items():
        err = abs(a[k] - b[k]) / max(abs(a[k]), abs(b[k]), 1e-30)
        say(f"{name}: {k} {a[k]!r} vs {b[k]!r}  rel {err:.3e}  "
            f"(tol {rtol:g})")
        if not err <= rtol:
            raise RuntimeError(f"{name}: {k} differs by {err:.3e} > {rtol}")


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def phase_train(compiles: CompileLog) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch
    from repro.launch.train import run

    cfg, args = settings("auto", STEPS, N_LAYERS, REFRESH_CHUNKS)
    say(f"[train] {ARCH} published widths, {cfg.n_layers} of 40 layers, "
        f"vocab {cfg.vocab}, batch {args.batch} x seq {args.seq}, "
        f"backend {args.backend}, refresh chunks {args.refresh_chunks}, "
        f"{args.steps} steps")
    records = []

    def on_step(rec):
        say(f"[train] step {rec['step']} {rec['program']}: "
            f"loss {rec['loss']!r} grad_norm {rec['grad_norm']!r} "
            f"update_norm {rec['update_norm']!r} ({rec['dt']:.2f} s)")
        records.append(finite_record(rec))

    out = run(cfg, args, label=f"[train] {ARCH}", on_step=on_step)
    for fam, (n, d) in out["precond_rows"].items():
        say(f"[train] {fam}: preconditioned {n} of {d} rows "
            f"({100 * n / d:.2f}%) in each step program")
    used = {r["program"] for r in records}
    n_fast = sum(r["program"] == "fast_step" for r in records)
    if records[0]["program"] != "train_step" or n_fast < MIN_FAST_STEPS:
        raise RuntimeError(f"expected step 1 on train_step and at least "
                           f"{MIN_FAST_STEPS} fast steps; got "
                           f"{[r['program'] for r in records]}")

    # the executables the loop ran: lowering again with the same argument
    # types finds them in the jit cache, so this compiles nothing
    params, state, batch = out["params"], out["state"], out["batch"]
    flags = {k: jnp.asarray(True) for k in out["opt"].stat_names()}
    call_args = {
        "train_step": (params, state, batch, flags, args.damping, 0.0, 0.0),
        "fast_step": (params, state, batch, args.damping, 0.0, 0.0)}
    for name in sorted(used):
        compiled = out["programs"][name].lower(*call_args[name]).compile()
        n_kernels = compiled.as_text().count(KERNEL_MARK)
        compiles.report("[train]", name)
        say(f"[train] {name}: {n_kernels} tpu_custom_call in the HLO")
        if n_kernels == 0:
            raise RuntimeError(f"{name}: no Pallas kernel in the compiled "
                               "step")
    for op, by_backend in dispatch.resolutions().items():
        for which, impl in by_backend.items():
            say(f"[train] dispatch {op}: resolved {which}, ran {impl}")
    say(f"[train] peak_bytes_in_use {peak_bytes()}")
    del out, params, state, batch
    jax.clear_caches()


def phase_check(compiles: CompileLog) -> None:
    from repro.launch.train import run

    first = {}
    for backend in ("ref", "auto"):
        cfg, args = settings(backend, 1, CHECK_LAYERS)
        recs = []
        out = run(cfg, args, label=f"[check] {backend}",
                  on_step=lambda r: recs.append(finite_record(r)))
        del out
        first[backend] = recs[0]
        say(f"[check] {backend}, {cfg.n_layers} layer: "
            f"loss {recs[0]['loss']!r} grad_norm {recs[0]['grad_norm']!r} "
            f"update_norm {recs[0]['update_norm']!r}")
        compiles.report(f"[check] {backend}", "train_step")
    compare("[check] ref vs auto", first["ref"], first["auto"], TOLERANCE)
    say(f"[check] peak_bytes_in_use {peak_bytes()}")


def phase_four_chips() -> None:
    """Data-parallel schedule (shard_map, FactorReducer reduce-scatter,
    sharded Stage-4 inversion) against the jit schedule with the batch
    sharded, on one 4-device mesh, from identical inputs: the refresh step
    of both must agree; the shard_map fast step then runs on the result.

    The shard_map region is manual over every mesh axis (``manual_axes=
    "all"``; the model axis has size 1), so the kernels run in it on each
    chip. Mosaic kernels cannot be partitioned by the compiler, so
    whatever runs under ``jit`` across the mesh (the whole jit schedule)
    resolves to ``ref`` (:func:`repro.kernels.dispatch.resolve`).
    Compilation is most of this phase's time, so the jit refresh program
    compiles in a second thread while the shard_map steps compile and run."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.ngd import NGDConfig, SPNGD
    from repro.data.synthetic import token_batches
    from repro.kernels import dispatch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (make_shardmap_fast_step,
                                    make_shardmap_train_step,
                                    make_train_step)
    from repro.models.transformer import DecoderLM

    cfg, args = settings("auto", 2, N_LAYERS)
    n = len(jax.devices())
    mesh = make_mesh((n, 1), ("data", "model"))
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    gbatch = n * BATCH
    lr, mom, lam = args.lr, 0.9, args.damping
    say(f"[four_chips] {ARCH} published widths, {cfg.n_layers} of 40 "
        f"layers, mesh data={n} x model=1, global batch {gbatch} x seq "
        f"{SEQ}, inverse {args.inverse_method}")

    def setup():
        """Model, optimizer and replicated params/state from the seed."""
        model = DecoderLM(cfg)
        opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                    model.site_counts,
                    NGDConfig(damping=lam, backend=cfg.backend,
                              inverse_method=args.inverse_method,
                              inverse_sharding=True))
        params = jax.device_put(model.init(jax.random.PRNGKey(0)), repl)
        return model, opt, params, jax.device_put(opt.init(params), repl)

    batches = token_batches(cfg.vocab, gbatch, SEQ, seed=0)
    b1 = jax.device_put(next(batches), data)
    b2 = jax.device_put(next(batches), data)
    model, opt, params, state = setup()
    flags = jax.device_put({k: jnp.asarray(True) for k in opt.stat_names()},
                           repl)
    call = (params, state, b1, flags, lam, lr, mom)
    # the jit schedule's arguments are placed exactly like these
    specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, call)
    with jax.set_mesh(mesh):
        sm_step = make_shardmap_train_step(model, opt, mesh,
                                           manual_axes="all")
        sm_fast = make_shardmap_fast_step(model, opt, mesh,
                                          manual_axes="all")
        jit_model = DecoderLM(cfg)
        jit_step = make_train_step(jit_model, SPNGD(
            jit_model.loss, jit_model.site_infos(), jit_model.fstats,
            jit_model.site_counts, opt.cfg))
        lowered = {
            "shardmap": jax.jit(sm_step, donate_argnums=(0, 1)).lower(*call),
            "jit": jax.jit(jit_step, donate_argnums=(0, 1)).lower(*specs)}

    def kernels(schedule, compiled):
        n_kernels = compiled.as_text().count(KERNEL_MARK)
        say(f"[four_chips] {schedule} train_step: {n_kernels} "
            f"tpu_custom_call in the HLO")
        if schedule == "shardmap" and n_kernels == 0:
            raise RuntimeError("shardmap train_step: no Pallas kernel in "
                               "the compiled step")
        return compiled

    def check(schedule, step, m):
        rec = finite_record({"step": step,
                             **{k: float(m[k]) for k in TOLERANCE}})
        say(f"[four_chips] {schedule} step {step}: loss {rec['loss']!r} "
            f"grad_norm {rec['grad_norm']!r} "
            f"update_norm {rec['update_norm']!r}")
        return rec

    with ThreadPoolExecutor(2) as pool:
        pending = {k: pool.submit(low.compile) for k, low in lowered.items()}
        exe = {"shardmap": kernels("shardmap", pending["shardmap"].result())}
        params, state, m1 = exe["shardmap"](*call)
        first = {"shardmap": check("shardmap", 1, m1)}
        placed = {"params": params, "factors": state["curv"], "batch": b1}
        for what, tree in placed.items():
            sets = {len(x.sharding.device_set) for x in jax.tree.leaves(tree)}
            say(f"[four_chips] shardmap: {what} on {sorted(sets)} device(s)")
            if sets != {n}:
                raise RuntimeError(f"shardmap: {what} not placed on all {n} "
                                   f"devices: {sets}")
        with jax.set_mesh(mesh):
            params, state, m2 = jax.jit(sm_fast, donate_argnums=(0, 1))(
                params, state, b2, lam, lr, mom)
        check("shardmap", 2, m2)
        del params, state, call
        exe["jit"] = kernels("jit", pending["jit"].result())

    _, _, params, state = setup()
    _, _, m1 = exe["jit"](params, state, b1, flags, lam, lr, mom)
    first["jit"] = check("jit", 1, m1)
    for op, by_backend in dispatch.resolutions().items():
        for which, impl in by_backend.items():
            say(f"[four_chips] dispatch {op}: resolved {which}, ran {impl}")
    compare("[four_chips] step 1 shardmap vs jit", first["shardmap"],
            first["jit"], FOUR_CHIP_TOLERANCE)
    say(f"[four_chips] peak_bytes_in_use {peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel schedule on 4 chips")
    opts = ap.parse_args(argv)

    # before the backend starts, as a cache directory given by the
    # environment would be
    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    compiles = CompileLog()

    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    say(f"device: {devices[0].platform} {kind} x{len(devices)}, "
        f"jax {jax.__version__}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    want = 4 if opts.four_chips else 1
    if len(devices) < want:
        raise SystemExit(f"chip_smoke: needs {want} chips, found "
                         f"{len(devices)}")

    say(f"compile cache: {cache_dir}")
    if opts.four_chips:
        phase_four_chips()
    else:
        phase_train(compiles)
        phase_check(compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": want}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

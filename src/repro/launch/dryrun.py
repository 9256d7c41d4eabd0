import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_BASE_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) pair, lower + compile the appropriate
step (train / prefill / single-token decode) against the production mesh —
16x16 single pod and 2x16x16 multi-pod — using ShapeDtypeStruct stand-ins
(no allocation), and record:

  * compiled.memory_analysis()  (bytes per device: does it fit)
  * compiled.cost_analysis()    (FLOPs / bytes for the roofline)
  * collective bytes parsed from the optimized HLO (roofline 3rd term)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_2_1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs, INPUT_SHAPES
from repro.configs.base import ArchConfig, InputShape
from repro.core.ngd import NGDConfig, SPNGD
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (analyze_hlo, roofline_terms,
                                   model_flops_train, model_flops_decode)
from repro.launch.train import (make_train_step, make_serve_step,
                                make_prefill_step, make_shardmap_train_step,
                                make_shardmap_fast_step, make_fast_step)
from repro.models.transformer import DecoderLM

LM_ARCHS = [a for a in list_archs() if a != "resnet50"]

# dense/MoE full-attention archs run long_500k with a sliding-window variant
SWA_FOR_LONG = 8192


def effective_config(arch: str, shape_name: str) -> Optional[ArchConfig]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape_name == "long_500k":
        if cfg.block_type in ("rwkv",):
            return cfg                     # O(1)-state: native
        if cfg.block_type == "hymba":
            # hybrid: SSM branch is O(1); attention branch gets a window
            return dataclasses.replace(cfg, sliding_window=SWA_FOR_LONG)
        if cfg.sliding_window == 0:
            # dense/moe full attention: run the documented SWA variant
            return dataclasses.replace(cfg, sliding_window=SWA_FOR_LONG)
    return cfg


def pick_accum(cfg: ArchConfig, shape: InputShape, data_shards: int) -> int:
    if shape.kind != "train":
        return 1
    per_shard = 1 if cfg.d_model >= 6144 else 4
    return max(1, shape.global_batch // (per_shard * data_shards))


def count_params(shapes) -> int:
    import math
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))


def active_param_fraction(cfg: ArchConfig) -> float:
    """Fraction of expert params active per token (MoE 6*N_active*D)."""
    if cfg.n_experts:
        # router dispatch: top_k of n_experts routed + shared always on
        return (cfg.top_k + cfg.n_shared_experts) / (
            cfg.n_experts + cfg.n_shared_experts)
    return 1.0


def build_case(arch: str, shape_name: str, mesh, *,
               schedule: str = "auto", tp_align: bool = False,
               rwkv_chunk: int = 0, fast: bool = False,
               backend: str = "auto", factor_dtype: str = "f32",
               inverse_method: str = "eigh", comm_strategy: str = "dense",
               wire_dtype: Optional[str] = None,
               devices_per_host: Optional[int] = None,
               inverse_sharding: bool = False,
               refresh_chunks: int = 1):
    """Returns (step_fn, example_args, n_params, label).

    schedule: "auto" (GSPMD everything — baseline) | "shardmap" (the paper's
    explicit 5-stage Algorithm 3). tp_align: factor blocks aligned to TP
    shard boundaries (beyond-paper, DESIGN.md §4). backend: kernel backend
    for the hot paths (repro.kernels.dispatch) — threaded through both the
    jit and shard_map schedules via the arch config and NGDConfig.
    factor_dtype: factor-history storage ("f32" | "bf16" | "fp8_e4m3" |
    "fp8_e5m2"; fp8 stores sym-packed payloads + per-block scales, so the
    dry-run's memory_analysis sees the compressed optimizer state).
    inverse_method: Stage-4 inversion ("eigh" | "cholesky" |
    "newton_schulz" — the matmul-only iteration the dry-run's cost_analysis
    then counts as GEMM FLOPs instead of an opaque eigendecomposition).
    comm_strategy/wire_dtype: Stage-3 factor reduce under the shardmap
    schedule (repro.comm) — the ring strategies swap the psum_scatter for
    ppermute hops, visible in the dry-run's collective-permute byte
    column. inverse_sharding: Stage-4 distribution (repro.comm.Stage4
    Inverter) — each device inverts only its reducer-owned factor chunk and
    the preconditioners all-gather (implies the double buffer), so the
    dry-run compiles the sharded refresh at production mesh scale.
    refresh_chunks: chunked refresh pipeline (repro.core.pipeline) — K>1
    compiles the capture step (no inline inversions; Stage-4 drains over
    the next K fast steps), so the dry-run's cost/memory analysis shows
    the overlapped step programs. Implies the double buffer."""
    cfg = effective_config(arch, shape_name)
    if backend != "auto":
        cfg = dataclasses.replace(cfg, backend=backend)
    if tp_align:
        cfg = dataclasses.replace(cfg, tp_shards=mesh.shape["model"])
    if rwkv_chunk:
        cfg = dataclasses.replace(cfg, scan_chunk=rwkv_chunk)
    shape = INPUT_SHAPES[shape_name]
    comm = None
    if schedule == "shardmap" and shape.kind == "train":
        from repro.comm import make_comm_config
        comm = make_comm_config(comm_strategy, wire_dtype,
                                backend=cfg.backend,
                                devices_per_host=devices_per_host)
        if comm.strategy == "fused" and not fast:
            # fused: the SYRK epilogue itself emits wire-format payloads —
            # thread the fp8 wire format into the capture specs so the
            # model's factor sums come out pre-packed
            cfg = dataclasses.replace(cfg, factor_wire=comm.wire_fmt or "")
    model = DecoderLM(cfg)
    dp = shd.dp_axes(mesh)
    data_shards = 1
    for a in dp:
        data_shards *= mesh.shape[a]

    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    # the paper's pure-DP schedule replicates weights (no TP) — use it for
    # archs that fit per device; keep GSPMD TP for the big ones
    sm_manual = "all" if cfg.d_model < 6144 else "dp"
    if schedule == "shardmap" and sm_manual == "all" and shape.kind == "train":
        p_specs = jax.tree.map(lambda _: P(), params_shape)
    else:
        p_specs = shd.params_pspecs(params_shape, cfg, mesh=mesh)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs)
    params_sds = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shape, p_sh)
    n_params = count_params(params_shape)

    batch_shape = model.input_specs(shape)

    # sequence-parallel residual constraint. NOT applied under the shardmap
    # schedule: mixing a seq-dim constraint with partial-manual axes trips an
    # XLA SPMD partitioner crash ("Invalid binary instruction opcode copy",
    # cf. the b/433785288 resharding path) on this toolchain.
    if cfg.d_model >= 2048 and schedule != "shardmap":
        def act_hook(h):
            if h.shape[1] >= mesh.shape["model"]:
                return jax.lax.with_sharding_constraint(
                    h, NamedSharding(mesh, P(dp, "model", None)))
            return h
        model.act_hook = act_hook

    # dispatch-buffer constraint is part of the optimized (--tp-align)
    # variant; baselines stay compiler-auto
    if cfg.n_experts and shape.kind == "train" and tp_align:
        def moe_hook(buf):                       # (E, C, d): keep d on TP
            return jax.lax.with_sharding_constraint(
                buf, NamedSharding(mesh, P(None, None, "model")))
        model.moe_hook = moe_hook

    if shape.kind == "train":
        from repro.quant import FACTOR_DTYPES
        opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                    model.site_counts,
                    NGDConfig(backend=cfg.backend,
                              inverse_method=inverse_method,
                              factor_dtype=FACTOR_DTYPES[factor_dtype],
                              inverse_sharding=inverse_sharding,
                              double_buffer=(inverse_sharding
                                             or refresh_chunks > 1),
                              refresh_chunks=refresh_chunks),
                    sharding_hook=shd.factor_sharding_hook(mesh))
        accum = pick_accum(cfg, shape, data_shards)
        if schedule == "shardmap":
            if sm_manual == "all":
                accum = max(1, shape.global_batch
                            // len(mesh.devices.flatten()))
            if cfg.factor_wire:
                accum = 1      # fp8 wire payloads cannot scan-accumulate
            if fast:
                step = make_shardmap_fast_step(model, opt, mesh, accum=accum,
                                               manual_axes=sm_manual,
                                               comm=comm)
            else:
                step = make_shardmap_train_step(model, opt, mesh,
                                                accum=accum,
                                                manual_axes=sm_manual,
                                                comm=comm)
        elif fast:
            step = make_fast_step(model, opt, accum=accum)
        else:
            step = make_train_step(model, opt, accum=accum)
        opt_shape = jax.eval_shape(opt.init, params_sds)
        o_specs = shd.opt_state_pspecs(opt_shape, p_specs, mesh)
        o_sh = jax.tree.map(lambda s: NamedSharding(mesh, s)
                            if isinstance(s, P) else s, o_specs,
                            is_leaf=lambda x: isinstance(x, P))
        opt_sds = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            opt_shape, o_sh)
        b_specs = shd.batch_pspecs(batch_shape, mesh)
        batch_sds = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                              sharding=NamedSharding(mesh, s)),
            batch_shape, b_specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        scal = jax.ShapeDtypeStruct((), jnp.float32)
        if fast:
            args = (params_sds, opt_sds, batch_sds, scal, scal, scal)
            return step, args, n_params, f"train-fast(accum={accum},{schedule})"
        flags = {k: jax.ShapeDtypeStruct((), jnp.bool_)
                 for k in opt.stat_names()}
        args = (params_sds, opt_sds, batch_sds, flags, scal, scal, scal)
        return step, args, n_params, f"train(accum={accum},{schedule})"

    if shape.kind == "prefill":
        step = make_prefill_step(model)
        b_specs = shd.batch_pspecs(batch_shape, mesh)
        batch_sds = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                              sharding=NamedSharding(mesh, s)),
            batch_shape, b_specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        return step, (params_sds, batch_sds), n_params, "prefill"

    # decode
    step = make_serve_step(model)
    b_specs = shd.batch_pspecs(batch_shape, mesh)
    batch_sds = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                          sharding=NamedSharding(mesh, s)),
        batch_shape, b_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return step, (params_sds, batch_sds["cache"], batch_sds["tokens"]), \
        n_params, "decode"


def run_case(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: Optional[str] = None, schedule: str = "auto",
             tp_align: bool = False, rwkv_chunk: int = 0,
             fast: bool = False, backend: str = "auto",
             factor_dtype: str = "f32",
             inverse_method: str = "eigh", comm_strategy: str = "dense",
             wire_dtype: Optional[str] = None,
             devices_per_host: Optional[int] = None,
             inverse_sharding: bool = False,
             refresh_chunks: int = 1) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = len(mesh.devices.flatten())
    shape = INPUT_SHAPES[shape_name]
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "schedule": schedule,
           "tp_align": tp_align, "backend": backend,
           "factor_dtype": factor_dtype, "inverse_method": inverse_method,
           "comm_strategy": comm_strategy,
           "inverse_sharding": inverse_sharding,
           "refresh_chunks": refresh_chunks,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": n_chips}
    try:
        with jax.set_mesh(mesh):
            step, args, n_params, label = build_case(
                arch, shape_name, mesh, schedule=schedule, tp_align=tp_align,
                rwkv_chunk=rwkv_chunk, fast=fast, backend=backend,
                factor_dtype=factor_dtype, inverse_method=inverse_method,
                comm_strategy=comm_strategy, wire_dtype=wire_dtype,
                devices_per_host=devices_per_host,
                inverse_sharding=inverse_sharding,
                refresh_chunks=refresh_chunks)
            reducer = getattr(step, "reducer", None)
            if reducer is not None:
                rec["comm"] = reducer.scatter_report()
                if reducer.template is not None:
                    rec["comm"]["wire_bytes_per_refresh"] = sum(
                        reducer.wire_bytes_per_stat().values())
                    levels = reducer.wire_bytes_per_stat_levels().values()
                    rec["comm"]["wire_intra_bytes_per_refresh"] = sum(
                        intra for intra, _ in levels)
                    rec["comm"]["wire_inter_bytes_per_refresh"] = sum(
                        inter for _, inter in levels)
                    # Stage-4 gather leg: bytes the preconditioner
                    # all-gather moves per refresh (0 when the inversion is
                    # replicated — nothing to gather)
                    rec["comm"]["gather_bytes_per_refresh"] = (
                        sum(reducer.gather_bytes_per_stat().values())
                        if inverse_sharding else 0)
                    rec["stage4"] = stage4_report(
                        reducer, inverse_sharding, inverse_method)
            lowered = jax.jit(step).lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        ana = analyze_hlo(hlo)
        # the compiled module is the per-device SPMD program: scale to global
        flops = float(ana.flops) * n_chips     # trip-weighted (see roofline.py)
        hbm = float(ana.hbm_bytes) * n_chips
        coll_total = float(ana.collective_bytes) * n_chips
        static_flops = float(cost.get("flops", 0.0))
        static_bytes = float(cost.get("bytes accessed", 0.0))
        cfg = effective_config(arch, shape_name)
        frac = active_param_fraction(cfg)
        n_active = n_params * frac if cfg.n_experts == 0 else _active_params(cfg)
        if shape.kind == "train":
            mflops = model_flops_train(n_active, shape.global_batch * shape.seq_len)
        elif shape.kind == "prefill":
            mflops = 2.0 * n_active * shape.global_batch * shape.seq_len
        else:
            mflops = model_flops_decode(n_active, shape.global_batch)
        terms = roofline_terms(flops, hbm, coll_total, n_chips)
        rec.update({
            "label": label, "status": "ok",
            "n_params": int(n_params), "n_params_active": int(n_active),
            "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
            "hlo_flops": flops, "hlo_bytes": hbm,
            "static_flops": static_flops, "static_bytes": static_bytes,
            "collective_bytes": coll_total,
            "collective_by_kind": ana.bytes_by_kind,
            "collective_counts": ana.count_by_kind,
            "model_flops": mflops,
            "useful_flops_ratio": (mflops / flops) if flops else None,
            "memory_analysis": _mem_dict(mem),
            **terms,
        })
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)
    except Exception as e:
        rec.update({"status": "fail", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
    return rec


def _active_params(cfg: ArchConfig) -> float:
    """Active params/token for MoE: non-expert params + top_k routed +
    shared experts."""
    d, ff, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    per_expert = 3 * d * ff
    routed_total = cfg.n_experts * per_expert * L
    shared_total = (3 * d * ff * cfg.n_shared_experts) * L
    gated = 3 if cfg.gated_mlp else 2
    attn = L * (2 * d * cfg.n_heads * cfg.hd + 2 * d * cfg.n_kv_heads * cfg.hd)
    emb = 2 * cfg.vocab * d
    other = attn + emb + L * d * cfg.n_experts  # router
    active = other + shared_total + L * cfg.top_k * per_expert
    return active


def stage4_report(reducer, inverse_sharding: bool, method: str) -> dict:
    """Per-layer Stage-4 inversion timing + gather bytes for the scatter
    report (make_report's §Stage-4 input). For every full-kind factor the
    reducer knows, invert ONE leading slice of a synthetic SPD stand-in
    with the configured method on the dry-run host — the dry run never
    materializes real factors — and scale by the layer count / scatter
    group, so the report can show the modelled replicated-vs-sharded
    refresh cost per layer without running a training step."""
    import math
    import time as _time

    import numpy as np

    from repro.comm.comm import _leaf_shape
    from repro.kernels import dispatch

    gather = reducer.gather_bytes_per_stat()
    rep = {"inverse_sharding": inverse_sharding, "method": method,
           "stats": {}}
    rng = np.random.RandomState(0)
    for fam, stats in reducer.template.items():
        for key, leaf in stats.items():
            if key not in ("a", "g") or not reducer.sym_fn(fam, key):
                continue
            shape = _leaf_shape(leaf)          # (lead..., nb, b, b)
            lead = shape[0]
            axes = reducer.scatter_axes(lead)
            p = reducer.group_size(axes) if axes else 1
            b = shape[-1]
            one = (1,) + tuple(shape[1:])      # one leading (layer) slice
            m = rng.randn(*one[:-1], b).astype(np.float32)
            spd = jnp.asarray(m @ np.swapaxes(m, -1, -2) / b
                              + 0.1 * np.eye(b, dtype=np.float32))
            fn = jax.jit(lambda s: dispatch.damped_inverse(
                s, jnp.asarray(1e-3, jnp.float32), method=method))
            fn(spd).block_until_ready()        # compile + warm
            t0 = _time.perf_counter()
            fn(spd).block_until_ready()
            us = (_time.perf_counter() - t0) * 1e6
            name = f"{fam}.{key}"
            rep["stats"][name] = {
                "block_shape": list(shape),
                "us_per_layer": us,
                "layers": int(lead),
                "group": int(p),
                "replicated_us_per_device": us * lead,
                "sharded_us_per_device": us * math.ceil(lead / p),
                "gather_bytes": int(gather.get(name, 0))
                if inverse_sharding else 0,
            }
    return rep


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--schedule", default="auto", choices=["auto", "shardmap"])
    ap.add_argument("--backend", default="auto",
                    choices=["ref", "pallas", "auto"],
                    help="kernel backend (repro.kernels.dispatch); pallas "
                         "includes the fused attention backward")
    from repro.quant import FACTOR_DTYPES
    ap.add_argument("--factor-dtype", default="f32",
                    choices=sorted(FACTOR_DTYPES),
                    help="factor-history storage dtype (repro.quant); fp8 "
                         "shrinks the optimizer-state arrays the dry-run's "
                         "memory_analysis accounts")
    ap.add_argument("--inverse-method", default="eigh",
                    choices=["eigh", "cholesky", "newton_schulz"],
                    help="Stage-4 factor inversion; newton_schulz is the "
                         "matmul-only blocked iteration (MXU-resident under "
                         "--backend pallas, eigh fallback for blocks that "
                         "fail to contract)")
    from repro.comm import STRATEGIES, WIRE_DTYPES
    ap.add_argument("--comm-strategy", default="dense", choices=STRATEGIES,
                    help="Stage-3 factor reduce under --schedule shardmap "
                         "(repro.comm): dense psum_scatter, ring "
                         "reduce-scatter over sym-packed triangles, "
                         "ring_fp8 fp8-wire hops, hier (two-level "
                         "intra-host/inter-host reduce), or fused "
                         "(pre-packed payloads from the SYRK epilogue)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=sorted(WIRE_DTYPES),
                    help="collective wire dtype; defaults to f32 for "
                         "dense/ring, fp8_e4m3 for ring_fp8/hier/fused")
    ap.add_argument("--devices-per-host", type=int, default=None,
                    help="hier host-topology model: width of the "
                         "full-precision intra-host level (default: "
                         "jax.local_device_count())")
    ap.add_argument("--inverse-sharding", action="store_true",
                    help="Stage-4 distribution (repro.comm.Stage4Inverter): "
                         "each device inverts only its reducer-owned factor "
                         "chunk and preconditioners all-gather; implies the "
                         "double buffer and records per-layer inverse "
                         "timing + gather bytes in the scatter report")
    ap.add_argument("--refresh-chunks", type=int, default=1,
                    help="chunked refresh pipeline (repro.core.pipeline): "
                         "K>1 compiles the capture step (no inline "
                         "inversions; the Stage-4 work drains over the "
                         "next K fast steps) — pair with --fast to see "
                         "the drain-step program. Implies the double "
                         "buffer")
    ap.add_argument("--tp-align", action="store_true")
    ap.add_argument("--rwkv-chunk", type=int, default=0)
    ap.add_argument("--fast", action="store_true",
                    help="Algorithm 1 no-refresh steady-state step")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="write one dryrun_case event per record (plus "
                         "per-case spans and the console mirror) to this "
                         "JSONL stream (repro.obs.MetricsLogger)")
    args = ap.parse_args()
    if args.comm_strategy != "dense" and args.schedule != "shardmap":
        # the GSPMD-auto schedule has no explicit Stage-3 collective; a
        # record tagged ring/ring_fp8 that actually measured GSPMD would lie
        ap.error("--comm-strategy requires --schedule shardmap")
    if args.inverse_sharding and args.schedule != "shardmap":
        # the sharded Stage-4 refresh rides the reducer's scatter layout,
        # which only exists under the explicit shardmap schedule
        ap.error("--inverse-sharding requires --schedule shardmap")

    archs = LM_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    variant = ""
    if args.schedule != "auto":
        variant += f"__{args.schedule}"
    if args.backend != "auto":
        variant += f"__{args.backend}"
    if args.factor_dtype != "f32":
        variant += f"__{args.factor_dtype}"
    if args.inverse_method != "eigh":
        variant += f"__{args.inverse_method}"
    if args.comm_strategy != "dense":
        variant += f"__{args.comm_strategy}"
        if args.wire_dtype:
            variant += f"__{args.wire_dtype}"
        if args.devices_per_host:
            variant += f"__dph{args.devices_per_host}"
    if args.inverse_sharding:
        variant += "__invshard"
    if args.refresh_chunks > 1:
        variant += f"__rc{args.refresh_chunks}"
    if args.tp_align:
        variant += "__tpalign"
    if args.rwkv_chunk:
        variant += f"__chunk{args.rwkv_chunk}"
    if args.fast:
        variant += "__fast"
    from repro.obs import MetricsLogger
    log = MetricsLogger(args.metrics_jsonl)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = (f"{arch}__{shape}__{'multi' if mp else 'single'}"
                       f"{variant}")
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    log.console(f"[skip] {tag}")
                    continue
                hlo_path = (os.path.join(args.out, tag + ".hlo.txt")
                            if args.save_hlo else None)
                with log.span(f"dryrun.{tag}"):
                    rec = run_case(arch, shape, mp, save_hlo=hlo_path,
                                   schedule=args.schedule,
                                   tp_align=args.tp_align,
                                   rwkv_chunk=args.rwkv_chunk,
                                   fast=args.fast,
                                   backend=args.backend,
                                   factor_dtype=args.factor_dtype,
                                   inverse_method=args.inverse_method,
                                   comm_strategy=args.comm_strategy,
                                   wire_dtype=args.wire_dtype,
                                   devices_per_host=args.devices_per_host,
                                   inverse_sharding=args.inverse_sharding,
                                   refresh_chunks=max(1, args.refresh_chunks))
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                log.emit("dryrun_case", tag=tag,
                         **{k: v for k, v in rec.items()
                            if k != "traceback"})
                status = rec["status"]
                extra = ("" if status != "ok" else
                         f" flops={rec['hlo_flops']:.3g}"
                         f" coll={rec['collective_bytes']:.3g}B"
                         f" bottleneck={rec['bottleneck']}"
                         f" compile={rec['compile_s']}s")
                log.console(f"[{status}] {tag}{extra}")
                if status != "ok":
                    log.console(rec["error"])
    log.close()


if __name__ == "__main__":
    main()

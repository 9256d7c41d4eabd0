"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   model sizes and optimizer settings; its
  ``family`` names ``families/<family>.py`` (how the program builds that
  model) and its ``reference`` names ``reference/<reference>.py`` (the plain
  float32 reference that decides ``correct``);
* ``traffic/<traffic>.json``  batch shape, accumulation, refresh interval;
* ``metrics/<metric>.py``     one reader per metric, end-to-end and
  per-layer alike: ``read(ctx)`` returns a number, or None where the run
  holds nothing to read. A metric split by the end-to-end metric it moves
  (``step.mfu.lm``, ``step.mfu.conv``) is read by ``metrics/<metric>.py``
  where that exists, else by the reader of its name without the last
  dotted part (``metrics/step.mfu.py``);
* ``limits/<workload>.json``  the limit of each number ``correct`` compares;
* ``peaks.json``              the chip's published peaks, by ``device_kind``.

A run builds the program once, drives it from the seed through its first
steps (whose outputs the reference checks), warms up to the end of a
refresh cycle, and times whole refresh cycles for at least ``--seconds``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import math
import os
import statistics
import sys
import time
import types
from contextlib import nullcontext
from typing import Any, Callable, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# a traced run profiles whole refresh cycles for at least this long (or
# --seconds, if shorter): a trace of a few seconds holds every op of a cycle
TRACE_WINDOW_S = 3.0
# the rule that leaves a leaf out of the parameter-change comparison:
# its reference gradient norm under this share of the median leaf's
NEGLIGIBLE_GRAD = 1e-3


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> types.ModuleType:
    name = "chipbench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reader_path(metric: str) -> str:
    """The reader of a metric: its own file, else its base name's."""
    own = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    if os.path.exists(own) or "." not in metric:
        return own
    return os.path.join(BENCH_DIR, "metrics",
                        metric.rsplit(".", 1)[0] + ".py")


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    """One workload with everything its name leads to."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def family(self) -> types.ModuleType:
        return importlib.import_module(
            f"chipbench.families.{self.config['family']}")

    @property
    def reference(self) -> types.ModuleType:
        return importlib.import_module(
            f"chipbench.reference.{self.config['reference']}")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=workload, chips=w["chips"],
        config=load_json(os.path.join(ROOT, cfg_entry["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       w["traffic"] + ".json")),
        limits=load_json(os.path.join(BENCH_DIR, "limits",
                                      workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


# ---------------------------------------------------------------------------
# weights and batches from the seed
# ---------------------------------------------------------------------------

def seed_key_data(seed: int):
    """Any whole number up to 2**64 as threefry key data (two uint32)."""
    import numpy as np
    seed = int(seed) % (1 << 64)
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def leaf_paths(tree) -> list[str]:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def make_init(template, leaf_init: Callable, out_shardings=None) -> Callable:
    """``init(key_data) -> params`` shaped like ``template``: every leaf
    drawn on the device from its own fold of the seed's key, in the leaf's
    dtype. One jitted call makes them all, on ``out_shardings`` where
    given."""
    import jax
    import jax.numpy as jnp
    paths = leaf_paths(template)
    leaves, treedef = jax.tree.flatten(template)

    def draw(key, rule, sds):
        if rule[0] == "ones":
            return jnp.ones(sds.shape, sds.dtype)
        if rule[0] == "zeros":
            return jnp.zeros(sds.shape, sds.dtype)
        return (jax.random.normal(key, sds.shape, jnp.float32)
                * rule[1]).astype(sds.dtype)

    def init(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        out = [draw(jax.random.fold_in(key, i), leaf_init(p, s.shape), s)
               for i, (p, s) in enumerate(zip(paths, leaves))]
        return jax.tree.unflatten(treedef, out)

    return jax.jit(init, out_shardings=out_shardings)


def make_batches(cell: Cell, n: int, out_shardings=None) -> Callable:
    import jax

    def batches(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        return cell.family.make_batches(jax.random.fold_in(key, 1 << 30),
                                        cell.traffic, cell.config, n)

    return jax.jit(batches, out_shardings=out_shardings)


def leaf_norms(tree):
    """Per-leaf f32 Frobenius norms, in :func:`leaf_paths` order."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def make_change_norms(init: Callable) -> Callable:
    """``change(params, key_data)``: per-leaf norm of params minus the
    weights the seed made, which are drawn again rather than kept."""
    import jax

    def change(params, key_data):
        p0 = init(key_data)
        return leaf_norms(jax.tree.map(
            lambda a, b: a.astype("float32") - b.astype("float32"),
            params, p0))

    return jax.jit(change)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Program:
    opt: Any
    step: Callable          # jitted make_train_step, donates params + state
    fast: Callable          # jitted make_fast_step, donates params + state
    init: Callable          # weights from the seed (jitted), on the chips
    init_state: Callable    # the optimizer's own init (jitted), on the chips
    damping: float
    lr: float
    mom: float
    # a cell on several chips: its batches' sharding, and the weights drawn
    # on one chip for the reference
    batch_sharding: Any = None
    ref_init: Optional[Callable] = None

    def controller(self, interval: int):
        """Algorithm 2's controller with its interval pinned."""
        from repro.core.stale import IntervalController
        return IntervalController(self.opt.stat_names(), alpha=0.1,
                                  min_interval=interval,
                                  max_interval=interval,
                                  bytes_per_stat=self.opt.stat_bytes())


def build_program(cell: Cell, wrap_step: Optional[Callable] = None
                  ) -> Program:
    """One chip: ``make_train_step`` / ``make_fast_step`` jitted as they
    are. Several chips: the data-parallel schedule, ``make_shardmap_train_step``
    / ``make_shardmap_fast_step`` on a ``(data=chips, model=1)`` mesh
    (:func:`_mesh_steps`). ``wrap_step(kind, fn)``, for tests only, plants
    a fault under the timed path before it is jitted."""
    import jax
    import jax.numpy as jnp

    from repro.core.ngd import NGDConfig, SPNGD
    from repro.launch.train import make_fast_step, make_train_step

    o = cell.config["optimizer"]
    model = cell.family.build_model(cell.config)
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts,
                NGDConfig(damping=o["damping"], backend=o["backend"],
                          inverse_method=o["inverse_method"],
                          factor_dtype=jnp.dtype(o["factor_dtype"]),
                          double_buffer=True,
                          refresh_chunks=o["refresh_chunks"],
                          inverse_sharding=cell.traffic.get("stage4")
                          == "sharded"))
    accum = cell.traffic["accum"]
    template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if cell.chips > 1:
        return _mesh_steps(cell, model, opt, template, wrap_step)
    step = make_train_step(model, opt, accum=accum)
    fast = make_fast_step(model, opt, accum=accum)
    if wrap_step is not None:
        step, fast = wrap_step("train", step), wrap_step("fast", fast)
    init = make_init(template, cell.family.leaf_init)
    return Program(
        opt=opt,
        step=jax.jit(step, donate_argnums=(0, 1)),
        fast=jax.jit(fast, donate_argnums=(0, 1)),
        init=init,
        init_state=jax.jit(opt.init),
        damping=float(o["damping"]), lr=float(o["lr"]),
        mom=float(o["momentum"]), ref_init=init)


def _mesh_steps(cell: Cell, model, opt, template,
                wrap_step: Optional[Callable]) -> Program:
    """The data-parallel schedule on ``cell.chips`` chips, wired as
    ``chip_smoke.py --four-chips`` wires it: Stage 1-2 on each chip's shard
    of the batch inside a shard_map manual over every mesh axis (so the
    Pallas kernels resolve as on one chip), Stage 3 by the default
    ``FactorReducer`` (gradient psum, dense psum_scatter of the factors),
    Stage 4 sharded by owner where the traffic says ``"stage4":
    "sharded"``. Weights and velocity are replicated, the factor state
    lies along its leading axis as the reducer scatters it
    (``launch/sharding.opt_state_pspecs``), each batch is split over
    ``data``; the steps' outputs are pinned to that layout, so every call
    after the first hits the same program."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import opt_state_pspecs
    from repro.launch.train import (make_shardmap_fast_step,
                                    make_shardmap_train_step)

    o, traffic = cell.config["optimizer"], cell.traffic
    if traffic.get("mesh", {}).get("data") != cell.chips:
        raise SystemExit(f"{cell.name}: traffic mesh {traffic.get('mesh')} "
                         f"does not split the batch over its {cell.chips} "
                         "chips")
    mesh = make_mesh((cell.chips, 1), ("data", "model"))
    accum = traffic["accum"]
    step = make_shardmap_train_step(model, opt, mesh, accum=accum,
                                    manual_axes="all")
    fast = make_shardmap_fast_step(model, opt, mesh, accum=accum,
                                   manual_axes="all")
    if wrap_step is not None:
        step, fast = wrap_step("train", step), wrap_step("fast", fast)
    repl = NamedSharding(mesh, P())
    specs = opt_state_pspecs(jax.eval_shape(opt.init, template),
                             jax.tree.map(lambda _: P(), template), mesh)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def on_mesh(fn):
        @functools.wraps(fn)
        def traced(*args):
            # the kernels' dispatch reads the mesh while tracing
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return fn(*args)
        return jax.jit(traced, donate_argnums=(0, 1),
                       out_shardings=(repl, state_sh, repl))

    return Program(
        opt=opt, step=on_mesh(step), fast=on_mesh(fast),
        init=make_init(template, cell.family.leaf_init, repl),
        init_state=jax.jit(opt.init, out_shardings=state_sh),
        damping=float(o["damping"]), lr=float(o["lr"]),
        mom=float(o["momentum"]),
        batch_sharding=NamedSharding(mesh, P("data")),
        ref_init=make_init(template, cell.family.leaf_init))


def train_step(prog: Program, ctrl, t: int, params, state, batch,
               annotate: bool):
    """One step exactly as ``repro.launch.train.run`` takes it: the
    controller's flags pick the program; a capture step reads back every
    statistic's two similarities for the controller."""
    import jax
    import jax.numpy as jnp

    def ann(name):
        return (jax.profiler.TraceAnnotation(name) if annotate
                else nullcontext())

    flags = ctrl.flags(t)
    if any(flags.values()):
        with ann("chipbench.dispatch train_step"):
            jflags = {k: jnp.asarray(v) for k, v in flags.items()}
            params, state, m = prog.step(params, state, batch, jflags,
                                         prog.damping, prog.lr, prog.mom)
        with ann("chipbench.controller update"):
            ctrl.update(t, flags, {k: (float(v[0]), float(v[1]))
                                   for k, v in m["sims"].items()})
    else:
        with ann("chipbench.dispatch fast_step"):
            params, state, m = prog.fast(params, state, batch,
                                         prog.damping, prog.lr, prog.mom)
        with ann("chipbench.controller update"):
            ctrl.update(t, flags, {})
    return params, state, m


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared, each a worst case (larger is worse):

    * ``loss``: the largest relative gap of a step's loss;
    * ``grad1``: the first gradient as the optimizer took it (the velocity
      after step 1 over the learning rate), by the worst leaf;
    * ``change``: the parameters' change over the checked steps, by the
      worst leaf, leaving out leaves whose reference gradient is under
      :data:`NEGLIGIBLE_GRAD` of the median leaf's.

    A leaf's gap is the gap between the two norms over the larger of the
    reference's norm of that leaf and of the median leaf."""
    def worst(p: dict, r: dict, keep) -> float:
        names = [k for k in r if keep(k)]
        med = statistics.median(r[k] for k in names)
        gaps = [abs(p[k] - r[k]) / max(r[k], med, 1e-30) for k in names]
        return max(gaps) if all(map(math.isfinite, gaps)) else math.inf

    loss = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    raw = ref["raw_grad1"]
    floor = NEGLIGIBLE_GRAD * statistics.median(raw.values())
    return {
        "loss": max(loss) if all(map(math.isfinite, loss)) else math.inf,
        "grad1": worst(prog["grad1"], ref["grad1"], lambda k: True),
        "change": worst(prog["change"], ref["change"],
                        lambda k: raw[k] >= floor),
    }


def left_out(ref: dict) -> list[str]:
    raw = ref["raw_grad1"]
    floor = NEGLIGIBLE_GRAD * statistics.median(raw.values())
    return sorted(k for k, v in raw.items() if v < floor)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def check_steps(traffic: dict, chunks: int) -> tuple[int, int]:
    """(steps the reference follows, steps of warm-up). The check runs to
    the first fast step after the first refresh activates (the capture at
    step 1 activates at step chunks + 2); warm-up then runs to the end of
    that refresh cycle, so the window starts on a capture step."""
    interval = traffic["interval"]
    if interval < chunks + 1:
        raise SystemExit(f"interval {interval} < refresh chunks + 1: a "
                         "drain would not finish before the next capture")
    n = chunks + 2
    if n % interval == 1:                 # the activation step captures
        n += 1
    warm = -(-n // interval) * interval
    return n, warm


class Session:
    """The program built once for a cell, and the seeded pieces every run
    of it uses. ``wrap_step``, for tests only, plants a fault under the
    timed path."""

    def __init__(self, cell: Cell, wrap_step: Optional[Callable] = None):
        import jax
        self.cell = cell
        traffic, chunks = cell.traffic, cell.config["optimizer"][
            "refresh_chunks"]
        self.interval = traffic["interval"]
        self.n_check, self.n_warm = check_steps(traffic, chunks)
        if traffic["pool"] < self.n_check:
            raise SystemExit(f"the pool of {traffic['pool']} batches is "
                             f"smaller than the {self.n_check} checked steps")
        self.prog = build_program(cell, wrap_step)
        self.make_pool = make_batches(cell, traffic["pool"],
                                      self.prog.batch_sharding)
        self.change_norms = make_change_norms(self.prog.init)
        self.norms = jax.jit(leaf_norms)
        # the reference runs on one chip, from weights and batches drawn
        # there; on one chip they are the program's own
        if self.prog.ref_init is self.prog.init:
            self.ref_pool, self.ref_change = self.make_pool, \
                self.change_norms
        else:
            self.ref_pool = make_batches(cell, traffic["pool"])
            self.ref_change = make_change_norms(self.prog.ref_init)

    def start(self, seed: int, annotate: bool = False):
        """Weights, optimizer state and batches from the seed; the checked
        steps and warm-up through the window's own call. Returns the
        program's readings and the live run ``(params, state, m, pool,
        ctrl)`` at the end of warm-up."""
        import jax
        import numpy as np
        prog, key_data = self.prog, seed_key_data(seed)
        params = prog.init(key_data)
        state = prog.init_state(params)
        pool = self.make_pool(key_data)
        ctrl = prog.controller(self.interval)
        paths = leaf_paths(params)
        losses = []
        for t in range(1, self.n_warm + 1):
            params, state, m = train_step(prog, ctrl, t, params, state,
                                          pool[(t - 1) % len(pool)],
                                          annotate)
            if t <= self.n_check:
                losses.append(m["loss"])
            if t == 1:
                grad1 = self.norms(state["velocity"])
            if t == self.n_check:
                change = self.change_norms(params, key_data)
        jax.block_until_ready((params, state, m))
        readings = {
            "loss": [float(x) for x in losses],
            "grad1": dict(zip(paths, (np.asarray(grad1) / prog.lr).tolist())),
            "change": dict(zip(paths, np.asarray(change).tolist())),
        }
        return readings, (params, state, m, pool, ctrl)

    def reference(self, seed: int, op_dtype=None, fault=None) -> dict:
        """The plain reference over the checked steps from the same seed
        (weights and batches drawn again): its readings, the parameter
        change measured as the program's is."""
        import numpy as np
        key_data = seed_key_data(seed)
        ref = self.cell.reference.run(
            self.cell.config, self.cell.traffic, self.prog.ref_init(key_data),
            self.ref_pool(key_data)[:self.n_check], self.n_check,
            op_dtype=op_dtype, fault=fault)
        params = ref.pop("params")
        ref["change"] = dict(zip(leaf_paths(params), np.asarray(
            self.ref_change(params, key_data)).tolist()))
        return ref


def device_info(cell: Cell, device_check: bool):
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": cell.chips}
    if not device_check:
        return dev, None
    if dev["platform"] != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {dev['platform']!r}); "
                         "nothing was run")
    if len(devices) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips; JAX found "
                         f"{len(devices)}")
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if dev["kind"] not in table:
        raise SystemExit(f"no peaks for device kind {dev['kind']!r} in "
                         "peaks.json")
    return dev, table[dev["kind"]]


def device_peak_bytes(chips: int) -> int:
    """The peak memory so far of the fullest of the first ``chips`` chips:
    ``peak_bytes_in_use`` (buffers) plus ``peak_bytes_reserved``, the
    region where the TPU runtime holds a program's temporaries, which
    ``peak_bytes_in_use`` leaves out."""
    import jax
    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats()
        say(f"memory_stats {dev.id}: {stats}")
        peaks.append(int(stats["peak_bytes_in_use"])
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, compile_log, device_check: bool = True,
             wrap_step: Optional[Callable] = None,
             trace_dir: Optional[str] = None) -> dict:
    import jax

    dev, peaks = device_info(cell, device_check)
    sess = Session(cell, wrap_step)
    interval = sess.interval
    say(f"[{cell.name}] seed {seed}: check {sess.n_check} steps, warm-up to "
        f"step {sess.n_warm}, refresh interval {interval}")
    program, (params, state, m, pool, ctrl) = sess.start(seed, trace)
    for line in compile_log.lines():
        say(f"[{cell.name}] {line}")
    compiles_before = compile_log.count
    setup_s = time.perf_counter() - t_start

    # ---- the timed window: whole refresh cycles ----
    min_s = min(seconds, TRACE_WINDOW_S) if trace else seconds
    if trace:
        jax.profiler.start_trace(trace_dir)
        window_ann = jax.profiler.TraceAnnotation("chipbench.window")
        window_ann.__enter__()
    t0 = time.perf_counter()
    t, window_losses = sess.n_warm, []
    while True:
        t += 1
        params, state, m = train_step(sess.prog, ctrl, t, params, state,
                                      pool[(t - 1) % len(pool)], trace)
        window_losses.append(m["loss"])
        if (t - sess.n_warm) % interval == 0 and \
                time.perf_counter() - t0 >= min_s:
            break
    jax.block_until_ready((params, state, m))
    window_s = time.perf_counter() - t0
    if trace:
        window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    steps = t - sess.n_warm
    if compile_log.count != compiles_before:
        for line in compile_log.lines(compiles_before):
            say(f"[{cell.name}] in the window: {line}")
        raise SystemExit(f"{compile_log.count - compiles_before} compile(s) "
                         "inside the timed window")
    window_losses = [float(x) for x in window_losses]
    failed = sum(not math.isfinite(x) for x in window_losses)
    memory_peak = device_peak_bytes(cell.chips) if device_check else 0
    say(f"[{cell.name}] window: {steps} steps ({steps // interval} refresh "
        f"cycles) in {window_s:.4f} s; losses first {window_losses[0]!r} "
        f"max {max(window_losses)!r} last {window_losses[-1]!r}; "
        f"device peak {memory_peak} bytes")

    ctx = types.SimpleNamespace(
        cell=cell, family=cell.family, config=cell.config,
        traffic=cell.traffic, peaks=peaks, chips=cell.chips,
        setup_s=setup_s, window_s=window_s, steps=steps,
        items=steps * cell.family.items_per_step(cell.traffic),
        item=cell.family.ITEM, captures=steps // interval,
        memory_peak_bytes=memory_peak, trace=None)
    breakdown = None
    if trace:
        from chipbench import trace as tr
        ctx.trace = tr.reduce_dir(trace_dir)
        say(f"[{cell.name}] trace: busy {ctx.trace.busy_s!r} s of "
            f"{ctx.trace.window_s!r} s; collectives "
            f"{ctx.trace.collective_s()!r} s, exposed "
            f"{ctx.trace.exposed_collective_s()!r} s (a chip's mean)")
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()

    # ---- correctness: free the program's state, then the reference ----
    del params, state, m, pool
    gc.collect()
    t_ref = time.perf_counter()
    ref = sess.reference(seed)
    numbers = compare(program, ref)
    say(f"[{cell.name}] reference: {time.perf_counter() - t_ref:.2f} s; "
        f"leaves left out of the change: {left_out(ref) or 'none'}")
    say(f"[{cell.name}] losses: program {program['loss']} reference "
        f"{ref['loss']}")
    compared = {k: v for k, v in numbers.items() if k in cell.limits}
    for k in sorted(set(numbers) - set(compared)):
        say(f"[{cell.name}] {k} {numbers[k]!r}: not compared (no limit)")
    correct = all(v <= cell.limits[k] for k, v in compared.items())

    metrics = {}
    if device_check:
        for spec in (cell.per_layer if trace else cell.end_to_end):
            reader = load_module(reader_path(spec["name"]))
            value = reader.read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    dev["memory_peak_bytes"] = memory_peak
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": v, "limit": cell.limits[k]}
                       for k, v in compared.items()}
    return result


def report_check(result: dict) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for k, v in result["check"].items():
        mark = "ok" if v["value"] <= v["limit"] else "OVER"
        say(f"check {k}: {v['value']!r} limit {v['limit']!r} {mark}")
    say(f"correct: {str(result['correct']).lower()}")

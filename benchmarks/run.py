"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV. Modules:
  convergence      Table 1 / Fig. 1  (NGD vs SGD steps-to-target)
  fisher_ablation  Fig. 5 technique ablation (emp/1mc x unitBN/fullBN x stale)
  stale_reduction  Table 2 reduction % + Fig. 6 byte series
  scaling          Fig. 5 time/step vs #devices (measured + comm model)
  kernels_bench    Pallas kernel contracts + ref-vs-pallas train_step A/B
  serve_bench      continuous-batching decode throughput vs concurrency

The kernels module additionally writes ``BENCH_kernels.json`` (repo root)
with both backends' step timings so later PRs have a perf trajectory to
compare against; serve_bench rows measured in the same invocation are
merged into it.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import traceback

import jax


def _emit_kernels_json(quick: bool) -> None:
    from benchmarks import kernels_bench, serve_bench
    if not kernels_bench.LAST_RESULTS:
        return
    results = dict(kernels_bench.LAST_RESULTS)
    # serve_bench (when it ran in this invocation) shares the snapshot so
    # the bench_compare gate sees serve.* rows; the private _curve blob
    # stays out — it goes to the standalone serve_curve.json artifact
    results.update({k: v for k, v in serve_bench.LAST_RESULTS.items()
                    if not k.startswith("_")})
    rec = {
        "quick": quick,
        "jax_backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "host": platform.machine(),
        "note": ("Pallas kernels run interpret=True on CPU: "
                 "train_step.pallas timings here measure the dispatch "
                 "plumbing, not TPU kernel speed"),
        "results": results,
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_kernels.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args, _ = ap.parse_known_args()

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (convergence, fisher_ablation, kernels_bench,
                            scaling, serve_bench, stale_reduction)
    modules = {
        "kernels_bench": kernels_bench,
        "serve_bench": serve_bench,
        "fisher_ablation": fisher_ablation,
        "stale_reduction": stale_reduction,
        "scaling": scaling,
        "convergence": convergence,
    }
    if args.only:
        modules = {args.only: modules[args.only]}

    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules.items():
        try:
            for r in mod.run(quick=args.quick):
                print(r, flush=True)
        except Exception as e:
            failed.append(name)
            print(f"{name}.ERROR,0.0,{type(e).__name__}", flush=True)
            traceback.print_exc(file=sys.stderr)
            continue
    # after the loop so a same-invocation serve_bench run lands in the
    # snapshot too (results merge in _emit_kernels_json)
    if "kernels_bench" in modules and "kernels_bench" not in failed:
        try:
            _emit_kernels_json(args.quick)
        except OSError as e:
            # read-only checkout etc.: the benchmark itself succeeded
            print(f"# BENCH_kernels.json not written: {e}",
                  file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Run from the root of a checkout on a machine with the TPU chips the cell
asks for. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``check``: each number the
correctness check compared, with its limit. The run exits non-zero, with no
result, when JAX finds no TPU or fewer chips than the cell needs.

JAX's persistent compilation cache lives at ``.chipbench_cache/jax`` in the
checkout, whatever ``JAX_COMPILATION_CACHE_DIR`` says, with no size cap:
a cache elsewhere, or one that evicts, makes every run compile again (the
4-layer LM's programs are some 200 MB). The profiler trace of a
``--trace 1`` run goes to ``.chipbench_cache/trace`` and is deleted once
read.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".chipbench_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the profiler trace of a --trace 1 run here")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)

    from chipbench import harness
    from chipbench.compile_log import CompileLog
    compiles = CompileLog()
    cache_dir = jax.config.jax_compilation_cache_dir
    print(f"compile cache: {cache_dir}, "
          f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
          " files", file=sys.stderr, flush=True)
    cell = harness.load_cell(args.workload)
    trace_dir = os.path.join(CACHE, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              compile_log=compiles, trace_dir=trace_dir)
    if args.trace:
        if args.keep_trace:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    harness.report_check(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

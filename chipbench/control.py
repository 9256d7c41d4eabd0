"""Readings that set a cell's correctness limits, in one process:

    python3 chipbench/control.py --workload NAME --seeds 1,2,... \\
        [--control-seeds 7,8,9] [--fault half_batch] [--fault drop_shard] \\
        [--out FILE]

For every ``--seeds`` seed, the program's checked steps (the same call and
feed as a run of ``run.py``) against the plain reference: the lower
readings. For every ``--control-seeds`` seed, the reference computed with
the configuration's control operand dtype put in the program's place: the
upper readings; and, with ``--fault``, the reference with that fault
planted. A control seed that is also a ``--seeds`` seed reuses its plain
reference. Each reading is one JSON line (also written to ``--out``). The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".chipbench_cache", "jax")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    harness.device_info(cell, True)
    sess = harness.Session(cell)
    with open(args.out or os.devnull, "a") as out:
        def emit(kind, seed, numbers, extra=None):
            line = json.dumps({"workload": cell.name, "kind": kind,
                               "seed": seed, **numbers, **(extra or {})})
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()

        refs = {}
        for seed in [int(s) for s in args.seeds.split(",") if s]:
            t0 = time.perf_counter()
            program, live = sess.start(seed)
            del live
            gc.collect()
            ref = refs[seed] = sess.reference(seed)
            emit("program", seed, harness.compare(program, ref),
                 {"seconds": time.perf_counter() - t0,
                  "left_out": harness.left_out(ref),
                  "losses": program["loss"], "ref_losses": ref["loss"]})
        op = cell.config["precision"]["control_operands"]
        for seed in [int(s) for s in args.control_seeds.split(",") if s]:
            ref = refs.get(seed) or sess.reference(seed)
            ctl = sess.reference(seed, op_dtype=op)
            emit("control", seed, harness.compare(ctl, ref), {"op_dtype": op})
            for fault in args.fault:
                bad = sess.reference(seed, fault=fault)
                emit(f"fault:{fault}", seed, harness.compare(bad, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())

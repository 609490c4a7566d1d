"""Readings that set the limit of ``correct``: the program on many seeds,
and the control in its place.

    python3 bench/readings.py --workload tp3p5_w32.bulk \
        --seeds 101,102,103 --seconds 10 [--control]

One process sets the cell up once, then for each seed makes that seed's
operands, runs a window of ``--seconds`` and checks every product, as a
run of ``bench/run.py`` does.  ``--control`` puts the reference computed
in float32 (:func:`bench.reference.control_products`) in the program's
place; it has to read mismatches on every seed.  The benchmark's own
runs never run this.  One JSON line per seed goes to standard output.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, harness, reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax
    cell = cells.resolve(args.workload)
    bench = harness.Bench(cell, rehearse=args.rehearse, t0=T0)
    try:
        if not args.rehearse:
            from repro.kernels import runtime
            runtime.enable_compilation_cache()
        bench.setup(seeds[0])
    except harness.BenchError as e:
        print(f"readings: cannot measure {args.workload}: {e}",
              file=sys.stderr)
        return 2
    if args.control:
        bench.multiply = jax.jit(reference.control_products)
        bench.warm()
    for i, seed in enumerate(seeds):
        if i:
            bench.make_operands(seed)
        bench.window(args.seconds)
        attempted, failed = bench.check()
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": args.control, "calls": len(bench.calls),
                          "attempted": attempted,
                          "checked": bench.checked,
                          "mismatched_products": failed,
                          "window_compiles": bench.window_compiles}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell and print its result as one JSON line.

    python3 bench/run.py --workload tp3p5_w32.bulk --seed 7 \
        --seconds 30 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Without a TPU,
with fewer chips than the cell asks for, or with the Pallas interpreter
forced, it prints no result and exits 2.  ``--rehearse`` runs the cell
on the CPU at a tiny batch to check paths and control flow; such a
result is never a measurement.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import statistics    # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, harness, trace, work  # noqa: E402

#: the numbers ``correct`` compares, with their limits: the products are
#: exact integers, so one product that differs from the reference fails
LIMITS = {"mismatched_products": 0}


def info(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the rehearsal batch")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the raw profiler trace to DIR")
    ap.add_argument("--stall-ms", type=float, metavar="MS",
                    help="count where the calling thread is when a call "
                         "has taken MS, and print it to standard error")
    return ap.parse_args(argv)


def read_metrics(run: harness.Run, entries) -> dict:
    """Each metric's reader; one that finds nothing is left out."""
    out = {}
    for m in entries:
        value = cells.reader(m["name"], run.root)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result(bench: harness.Bench, run: harness.Run, attempted: int,
           failed: int, traced: bool) -> dict:
    cell = bench.cell
    res = {"correct": failed <= LIMITS["mismatched_products"],
           "attempted": attempted, "failed": failed}
    entries = cell.per_layer if traced else cell.end_to_end
    res["metrics"] = read_metrics(run, entries)
    dev = bench.devices[0]
    res["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(bench.devices),
                     "memory_peak_bytes": run.memory_peak_bytes}
    if traced:
        res["device"]["busy_s"] = run.trace.mean_busy_s()
        res["device"]["window_s"] = run.trace.window_s
        res["breakdown"] = trace.breakdown(run.trace)
    res["checks"] = {"mismatched_products": {
        "value": failed, "limit": LIMITS["mismatched_products"]}}
    return res


def measure(bench: harness.Bench, seconds: float, traced: bool,
            keep_trace: str | None = None) -> dict:
    """The window, the memory reading, the check, and the result."""
    bench.window(seconds, trace=traced, keep_trace=keep_trace)
    run = bench.record(bench.memory_peak_bytes())
    lat = sorted((r - i) * 1e3 for i, _, r in bench.calls)
    q1, med, q3 = statistics.quantiles(lat, n=4) if len(lat) > 1 \
        else lat * 3
    info(f"window_s={bench.window_s} calls={len(bench.calls)} "
         f"latency_ms samples={len(lat)} median={med} q1={q1} q3={q3} "
         f"max={lat[-1]} window_compiles={bench.window_compiles} "
         f"gc_collections={bench.gc.collections} "
         f"gc_longest_pause_s={bench.gc.longest_s}")
    if traced:
        info(f"trace stopped and read in {bench.trace_read_s} s")
    slow = sorted(bench.calls, key=lambda c: c[0] - c[2])[:5]
    info("slowest calls (ms, of it in garbage collection): " + " ".join(
        f"{(r - i) * 1e3:.3f}/{bench.gc.within(i, r) * 1e3:.3f}"
        for i, _, r in slow))
    for where, n in (bench.stall_stacks or {}).items():
        info(f"stalled {n}x at {where}")
    attempted, failed = bench.check()
    info(f"checked_products={bench.checked} of attempted={attempted}")
    res = result(bench, run, attempted, failed, traced)
    for name, c in res["checks"].items():
        info(f"check {name}={c['value']} limit={c['limit']}")
    return res


def main(argv=None) -> int:
    args = parse(argv)
    cell = cells.resolve(args.workload)
    bench = harness.Bench(cell, rehearse=args.rehearse, t0=T0)
    if args.stall_ms:
        bench.stall_s = args.stall_ms * 1e-3
    try:
        if not args.rehearse:
            from repro.kernels import runtime
            info(f"compilation cache {runtime.enable_compilation_cache()}")
        bench.setup(args.seed)
    except harness.BenchError as e:
        print(f"bench: cannot measure {args.workload}: {e}", file=sys.stderr)
        return 2
    info(f"{cell.name}: {bench.design.plan.describe()} batch={bench.batch} "
         f"rows={bench.rows} resident={cell.traffic['resident']} "
         f"chips={bench.chips} limbs={bench.la}x{bench.lb} "
         f"limb_products_per_product={bench.la * bench.lb} "
         f"interface_bytes_per_call="
         f"{work.interface_bytes(bench.rows, bench.la, bench.lb)}")
    info("setup phases_s " + " ".join(
        f"{k}={v}" for k, v in bench.phases.items()))
    info(f"setup_s={bench.setup_s} generate_s={bench.generate_s} "
         f"setup_peak_bytes={bench.setup_peak_bytes} "
         f"compile_s={bench.compile_s} compiles={bench.compiles} "
         f"cache_hits={bench.cache_hits} cache_misses={bench.cache_misses}")
    res = measure(bench, args.seconds, bool(args.trace), args.keep_trace)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

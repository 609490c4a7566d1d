"""Bank execution benchmark: planner design points run for real.

For each (bits, TP) design point from the paper's fractional-throughput
use cases (Sec. V-B / V-E, Table VIII widths), build the planner's bank,
execute a batch through ``core.bank``, and record

  * measured throughput (ops/cycle from the dispatch schedule) vs the
    plan's claimed throughput,
  * per-scheduler makespans (round_robin / greedy / streaming) so the
    policy comparison is tracked per PR -- greedy's earliest-completion
    dispatch must never lose to round-robin,
  * bit-exactness of the executed batch vs the Python-int oracle, on
    BOTH the core path and the fused megakernel path,
  * wall clock per execution backend (core / per-instance kernel /
    fused megakernel): compile cost and steady-state separately, the
    traced Pallas launch count of one bank round, and the
    fused-vs-per-instance speedup (the dispatch-tax payoff),
  * the per-step VMEM working set (the TPU 'area') vs the
    round-up-to-integer Star bank,
  * the planner's ASIC-area estimate vs the conventional Star bank.

Every design is constructed through the ``repro.designs`` facade, and
each emitted row embeds its serialized ``DesignSpec`` so the BENCH
artifact carries full, recompilable provenance
(``DesignSpec.from_dict(row["design_spec"])`` -> the same design).

Emits ``BENCH_bank.json`` (repo root, override with --out) and the
harness CSV rows; the JSON's ``fields`` header documents every
wall-clock column.  ``--smoke`` runs a 6-point subset for CI and
additionally ASSERTS the fused contract: launch_count == 1 on every
point and steady-state speedup >= 1.0 on at least one multi-instance
point.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time
from fractions import Fraction

import numpy as np
import jax
import jax.numpy as jnp

from repro import designs
from repro.core import limbs as L
from repro.core import planner, bank
from repro.core.bank import Bank
from repro.kernels import runtime
from repro.kernels.mcim_fold import vmem_bytes_per_step
from repro.verify import dataflow

RNG = np.random.default_rng(17)

#: execution backends every design point is timed on
TIMED_BACKENDS = ("core", "kernel", "fused")

#: documentation of the wall-clock fields, embedded in the JSON header
FIELDS = {
    "wall_us_first_call":
        "wall time of the first execute() call (us): includes trace + "
        "compile + one run; kept raw so compile cost is reconstructible",
    "wall_us_steady":
        "median wall time of 5 post-warmup execute() calls (us): the "
        "per-batch execution cost",
    "wall_us_compile":
        "wall_us_first_call - wall_us_steady, clamped at 0 (us): the "
        "one-time trace/compile cost a serving process pays once",
    "launch_count":
        "Pallas launches one bank round issues, counted in the traced "
        "jaxpr: 0 on core (pure jnp), one per busy instance on kernel, "
        "exactly 1 on fused",
    "fused_speedup_vs_kernel":
        "kernel wall_us_steady / fused wall_us_steady: >1 means the "
        "fused megakernel beats the per-instance launch tax",
    "paths":
        "per-backend timing dict {core|kernel|fused: {wall_us_*, "
        "launch_count}}; top-level wall_us_* columns are the core path",
    "vmem_bytes_step":
        "static per-grid-step VMEM residency of the fused megakernel "
        "launch (bytes), measured from the traced kernel jaxpr by the "
        "dataflow analyzer -- the TPU analogue of the paper's folded "
        "silicon area, exact and execution-free",
    "arith_intensity":
        "static FLOPs / HBM-bytes of one fused bank launch, from the "
        "dataflow analyzer's jaxpr interpretation (FLOPs) and "
        "block-index transition counting (bytes); positions each "
        "design point on the roofline without running it",
}

# Paper use cases: pure fractional TPs (one folded instance), the
# headline TP=3.5 mixed bank, and the Sec. V-B CT combination 5/6.
DESIGN_POINTS = [
    (bits, tp)
    for bits in (16, 32, 64, 128)
    for tp in (Fraction(1, 2), Fraction(1, 3), Fraction(1, 4),
               Fraction(1, 6), Fraction(7, 2), Fraction(5, 6))
]

SMOKE_POINTS = [
    (bits, tp)
    for bits in (16, 32)
    for tp in (Fraction(1, 2), Fraction(7, 2), Fraction(5, 6))
]

def _row(name, us, derived):
    print(f"{name},{us:.2f},{derived}")


def _time_path(bk: Bank, a, b) -> dict:
    """Wall-clock one backend path: first call, steady median, compile.

    The old ``wall_us_first_call`` column conflated compile and run
    time; ``wall_us_compile`` is the split-out one-time cost (first
    minus steady, clamped at 0 for paths whose first call happens to
    race under the median).
    """
    t0 = time.perf_counter()
    out = bk.execute(a, b)
    jax.block_until_ready(out)
    first = (time.perf_counter() - t0) * 1e6
    steady = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(bk.execute(a, b))
        steady.append((time.perf_counter() - t0) * 1e6)
    steady_us = float(np.median(steady))
    return {
        "wall_us_first_call": first,
        "wall_us_steady": steady_us,
        "wall_us_compile": max(first - steady_us, 0.0),
    }, out


def run_design_point(bits: int, tp: Fraction, batch_mult: int = 4) -> dict:
    spec = designs.DesignSpec(bits, bits, tp, backend="core")
    design = designs.generate(spec)
    plan, bk = design.plan, design.bank
    batch = batch_mult * max(tp.numerator, 1)

    a = jnp.asarray(L.random_limbs(RNG, (batch,), bits))
    b = jnp.asarray(L.random_limbs(RNG, (batch,), bits))

    expect = [L.from_limbs(np.asarray(x)) * L.from_limbs(np.asarray(y))
              for x, y in zip(a, b)]

    # every execution backend over the SAME plan/batch: core (pure
    # jnp), per-instance Pallas launches, and the fused megakernel
    paths = {}
    exact = fused_exact = False
    for name in TIMED_BACKENDS:
        pbk = bk if name == "core" else Bank(plan, bits, bits,
                                             backend=name)
        timing, out = _time_path(pbk, a, b)
        timing["launch_count"] = pbk.launch_count(batch)
        paths[name] = timing
        got = L.batch_from_limbs(np.asarray(out)) == expect
        if name == "core":
            exact = got
        elif name == "fused":
            fused_exact = got
    fused_speedup = (paths["kernel"]["wall_us_steady"] /
                     paths["fused"]["wall_us_steady"])

    rep = bk.last_report
    # scheduler policy comparison on the same (cts, batch) instance set;
    # streaming gets a real arrival trace (ceil(TP) ops/cycle, the rate
    # the bank is provisioned for) -- with all ops at cycle 0 it would
    # just reproduce round_robin
    cts = tuple(cfg.ct for cfg in bk.instances)
    rate = max(1, math.ceil(tp))
    streaming = bank.StreamingScheduler(arrival_rate=rate)
    makespans = {
        "round_robin": bank.round_robin_schedule(cts, batch)[1],
        "greedy": bank.greedy_schedule(cts, batch)[1],
        "streaming": streaming.schedule(cts, batch)[1],
    }
    # conventional bank: ceil(TP) Star instances
    n_star = max(1, math.ceil(tp))
    la = L.n_limbs_for_bits(bits)
    star_ws = n_star * vmem_bytes_per_step(la, la, 1, bk.tile_b)
    conv_area = planner.star_bank_area(bits, bits, tp)
    # static roofline of the fused launch (dataflow analyzer, cached)
    static = dataflow.plan_static_stats(bits, bits, plan.configs)
    return {
        "bits": bits,
        "tp": str(tp),
        "design_spec": spec.to_dict(),   # recompilable provenance
        "backend": design.bank.backend,
        "plan": plan.describe(),
        "latency_cycles": design.latency_cycles,
        "fmax_estimate_ghz": design.fmax_estimate,
        "instances": [
            {"arch": ir.config.arch, "ct": ir.ct, "n_ops": ir.n_ops,
             "busy_cycles": ir.busy_cycles}
            for ir in rep.instances],
        "batch": batch,
        "cycles": rep.cycles,
        "measured_throughput": str(rep.measured_throughput),
        "plan_throughput": str(rep.plan_throughput),
        "utilization": rep.utilization,
        "scheduler_makespans": makespans,
        "streaming_arrival_rate": rate,
        "greedy_vs_round_robin": makespans["greedy"] / makespans["round_robin"],
        "bit_exact": bool(exact),
        "fused_bit_exact": bool(fused_exact),
        "working_set_bytes": rep.working_set_bytes,
        "star_bank_working_set_bytes": star_ws,
        "working_set_saving": 1 - rep.working_set_bytes / star_ws,
        "vmem_bytes_step": static["vmem_bytes_step"],
        "arith_intensity": static["arith_intensity"],
        "area_um2": plan.area,
        "star_bank_area_um2": conv_area,
        "area_saving": 1 - plan.area / conv_area,
        "energy_per_op_pj": design.energy_per_op_pj,
        "peak_power_mw": design.peak_power_mw,
        # top-level wall-clock columns = the core path (see FIELDS)
        "wall_us_first_call": paths["core"]["wall_us_first_call"],
        "wall_us_compile": paths["core"]["wall_us_compile"],
        "wall_us_steady": paths["core"]["wall_us_steady"],
        "paths": paths,
        "launch_count": {name: p["launch_count"]
                         for name, p in paths.items()},
        "fused_speedup_vs_kernel": fused_speedup,
        "n_instances": len(bk.instances),
    }


def _assert_fused_smoke(results) -> None:
    """The CI fused contract: one launch everywhere, a real speedup
    somewhere.

    Every point's fused path must trace to exactly one Pallas launch;
    and on at least one multi-instance point the fused steady-state
    must beat (or tie) the per-instance kernel path -- interpret-mode
    wall clock is noisy per point, so the speedup gate takes the max
    over the multi-instance subset rather than demanding every point
    win.
    """
    bad = [(r["bits"], r["tp"]) for r in results
           if r["launch_count"]["fused"] != 1]
    assert not bad, f"fused path issued != 1 launch on points {bad}"
    assert all(r["fused_bit_exact"] for r in results), \
        "fused path lost bit-exactness on a smoke point"
    multi = [r for r in results if r["n_instances"] > 1]
    assert multi, "smoke grid has no multi-instance design point"
    best = max(r["fused_speedup_vs_kernel"] for r in multi)
    assert best >= 1.0, \
        (f"fused megakernel never reached per-instance parity on any "
         f"multi-instance smoke point (best speedup {best:.2f}x)")
    # static roofline columns: the dataflow analyzer must place every
    # point on the roofline (positive intensity, nonzero residency)
    bad = [(r["bits"], r["tp"]) for r in results
           if not (r.get("vmem_bytes_step", 0) > 0
                   and r.get("arith_intensity", 0) > 0)]
    assert not bad, \
        f"dataflow static roofline columns missing/zero on points {bad}"
    _row("bank.fused_smoke_gate", 0.0,
         f"launches_ok=True best_multi_instance_speedup={best:.2f}x "
         f"static_roofline_ok=True")


def bench_bank(out_path: str | None = None, smoke: bool = False):
    """Execute every design point; emit CSV rows + BENCH_bank.json."""
    points = SMOKE_POINTS if smoke else DESIGN_POINTS
    results = []
    for bits, tp in points:
        r = run_design_point(bits, tp)
        results.append(r)
        ms = r["scheduler_makespans"]
        _row(f"bank.{bits}b_tp{tp.numerator}_{tp.denominator}",
             r["wall_us_steady"],
             f"exact={r['bit_exact']} fused_exact={r['fused_bit_exact']} "
             f"util={r['utilization']:.3f} "
             f"cycles={r['cycles']} "
             f"rr={ms['round_robin']} greedy={ms['greedy']} "
             f"stream={ms['streaming']} "
             f"ws_saving={r['working_set_saving']:.0%} "
             f"area_saving={r['area_saving']:.0%} "
             f"E={r['energy_per_op_pj']:.2f}pJ "
             f"launches={r['launch_count']['kernel']}->"
             f"{r['launch_count']['fused']} "
             f"fused_speedup={r['fused_speedup_vs_kernel']:.2f}x")
    if smoke:
        _assert_fused_smoke(results)
    path = out_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_bank.json")
    with open(path, "w") as f:
        json.dump({"fields": FIELDS,
                   "interpret_mode": runtime.interpret_mode(),
                   "design_points": results, "smoke": smoke}, f, indent=1)
    _row("bank.artifact", 0.0, f"wrote={path} n={len(results)}")
    return results


ALL = [bench_bank]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("out", nargs="?", default=None,
                    help="output JSON path (default: repo-root "
                         "BENCH_bank.json)")
    ap.add_argument("--out", dest="out_flag", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: 6 design points")
    args = ap.parse_args()
    runtime.enable_compilation_cache()
    print("name,us_per_call,derived")
    bench_bank(args.out_flag or args.out, smoke=args.smoke)

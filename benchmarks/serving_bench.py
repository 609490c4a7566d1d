"""Online serving benchmark: SLO goodput under sustained load.

For each registry design point, sweep offered load below and above the
provisioned ``Plan.throughput`` and drive seeded synthetic traffic
through the full ``repro.serving`` worker loop (SLO admission control,
EDF dispatch in bank rounds, work stealing, optional autoscaling),
recording per point

  * offered rate vs achieved goodput (deadline-met completions/cycle),
  * p50/p99 end-to-end latency, in bank cycles AND in wall ns at the
    design's modeled fmax,
  * SLO-violation and refusal rates (violations must be zero by
    construction: the admission controller refuses instead),
  * per-instance utilization over the serving horizon,
  * work-steal counts and bank-round counts,
  * bit-exactness of every response vs the Python-bigint oracle.

Two scenario rows ride along: a 2-replica bursty trace with a skewed
router (every request homes to replica 0) so work stealing is load
bearing, and a diurnal trace under the EMA autoscaler so the replica
timeline is tracked per PR.

Emits ``BENCH_serving.json`` (repo root, override with --out) and the
harness CSV rows.  ``--smoke`` runs the reduced sweep for CI and
ASSERTS the serving contract: zero SLO violations everywhere, zero
refusals at offered load <= provisioned TP, graceful goodput
degradation (not collapse) above it, bit-exact responses on every
point, steals > 0 on the skewed scenario, scale-up on the diurnal
scenario, and one fused Pallas launch per bank round.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os

from repro import designs
from repro.core.bank import Bank
from repro.kernels import runtime
from repro.serving import (Autoscaler, Worker, bursty_arrivals,
                           diurnal_arrivals, poisson_arrivals, synthesize)

#: registry design points served: the paper's headline fractional-TP
#: mixed bank, a pure folded point, and the wide 5/6 CT-combination
DESIGN_POINTS = ("tbl8_w32_relaxed", "tp3p5_w32", "tp5over6_w128")

#: offered load as a multiple of the provisioned throughput; the
#: critical point rho=1.0 is swept in full runs but never gated (its
#: queue is divergent by definition)
FULL_LOADS = (0.5, 0.8, 1.0, 1.5, 2.0)
SMOKE_LOADS = (0.5, 0.8, 2.0)

N_REQUESTS = 400
N_SMOKE = 120

#: documentation of the emitted columns, embedded in the JSON header
FIELDS = {
    "load_factor":
        "offered rate / provisioned per-replica Plan.throughput; <1 is "
        "the under-provisioned regime the zero-refusal gate covers",
    "offered_rate":
        "measured requests/cycle over the serving horizon (first "
        "arrival to last retire) -- the realized, not nominal, load",
    "goodput":
        "deadline-met completions/cycle over the same horizon; above "
        "saturation this must hold near the provisioned TP (graceful "
        "degradation), never collapse",
    "p50_cycles / p99_cycles":
        "end-to-end latency percentiles (arrival to retire) of admitted "
        "requests, in bank cycles, from the shared "
        "core.bank.schedule histogram path",
    "p50_ns / p99_ns":
        "the same percentiles in wall time at the design's modeled "
        "fmax_estimate (cycles / GHz)",
    "slo_violation_rate":
        "admitted requests retired past their deadline / admitted; "
        "structurally 0: admission control refuses instead of missing",
    "refusal_rate":
        "refused / offered; every refusal carries its infeasibility "
        "evidence (earliest_possible > deadline)",
    "utilization":
        "per replica, per instance: busy cycles / horizon",
    "steals":
        "commits rebalanced across replicas by the work stealer",
    "rounds":
        "bank rounds dispatched (one Bank.execute -- one fused Pallas "
        "launch on the fused backend -- per replica per window)",
    "fused_launches_per_round":
        "Pallas launches one bank round traces to on the fused backend "
        "at the largest observed round batch (must be exactly 1)",
    "bit_exact":
        "every response checked against the Python-bigint oracle",
}


def _row(name, us, derived):
    print(f"{name},{us:.2f},{derived}")


def _report_row(name, load, budget, rep) -> dict:
    design = designs.generate(name)
    ghz = design.fmax_estimate
    p50, p99 = rep.latency_p50, rep.latency_p99
    return {
        "design": name,
        "design_spec": design.spec.to_dict(),
        "plan": design.plan.describe(),
        "provisioned_tp": rep.provisioned_tp,
        "load_factor": load,
        "budget_cycles": budget,
        "n_requests": rep.n_requests,
        "n_admitted": rep.n_admitted,
        "n_refused": rep.n_refused,
        "offered_rate": rep.offered_rate,
        "goodput": rep.goodput,
        "p50_cycles": p50,
        "p99_cycles": p99,
        "p50_ns": None if p50 is None else p50 / ghz,
        "p99_ns": None if p99 is None else p99 / ghz,
        "slo_violations": rep.slo_violations,
        "slo_violation_rate": rep.slo_violation_rate,
        "refusal_rate": rep.refusal_rate,
        "utilization": [list(u) for u in rep.utilization],
        "steals": rep.steals,
        "rounds": rep.rounds,
        "max_round_batch": rep.max_round_batch,
        "horizon_cycles": rep.horizon_cycles,
        "replica_timeline": [list(t) for t in rep.replica_timeline],
        "wall_s": rep.wall_s,
        "n_checked": rep.n_checked,
        "bit_exact": rep.bit_exact,
    }


def _budget(design) -> int:
    """SLO budget in cycles for one design: generous vs the transient
    queues of sub-critical load (which stretch with 1/TP -- service is
    slow relative to arrival bursts on low-TP banks), tight vs the
    divergent queue of sustained overload, so refusals appear exactly
    where queueing theory says they must."""
    tp = float(design.plan.throughput)
    max_ct = max(cfg.ct for cfg in design.bank.instances)
    return max(4 * max_ct, math.ceil(32 / tp))


def run_sweep_point(name: str, load: float, n: int, seed: int) -> dict:
    """One (design, load-factor) cell: Poisson traffic, 1 replica."""
    design = designs.generate(name)
    tp = float(design.plan.throughput)
    budget = _budget(design)
    arr = poisson_arrivals(n, load * tp, seed=seed)
    reqs = synthesize(arr, design.spec.bits_a, design.spec.bits_b,
                      budget=budget, seed=seed + 1)
    rep, _ = design.serve(reqs, check=True)
    return _report_row(name, load, budget, rep)


def run_steal_scenario(name: str, n: int, seed: int) -> dict:
    """2 replicas, bursty traffic, a skewed front-end router.

    The worker's router homes request ``rid % n_live``; giving every
    request an even rid pins the whole stream to replica 0, so ONLY the
    work stealer can use replica 1.  The gate asserts it does.
    """
    design = designs.generate(name)
    tp = float(design.plan.throughput)
    budget = 2 * _budget(design)     # 2 replicas: twice the capacity
    arr = bursty_arrivals(n, 1.2 * tp, seed=seed, burst=8)
    reqs = tuple(dataclasses.replace(r, rid=2 * r.rid)
                 for r in synthesize(arr, design.spec.bits_a,
                                     design.spec.bits_b,
                                     budget=budget, seed=seed + 1))
    rep, _ = design.serve(reqs, replicas=2, check=True)
    row = _report_row(name, 1.2, budget, rep)
    row["scenario"] = "steal_skewed_router"
    row["replicas"] = 2
    return row


def run_autoscale_scenario(name: str, n: int, seed: int) -> dict:
    """Diurnal traffic peaking above one replica's TP, EMA autoscaler.

    Run on a low-TP design so the trace spans many dispatch windows
    (the EMA needs windows to track the envelope up and back down);
    ``ema=0.6`` reacts within ~2 windows of a rate change.
    """
    design = designs.generate(name)
    tp = float(design.plan.throughput)
    budget = 4 * _budget(design)     # autoscale absorbs load, SLO lax
    scaler = Autoscaler(design.plan.throughput, min_replicas=1,
                        max_replicas=4, ema=0.6, patience=2)
    arr = diurnal_arrivals(n, 1.2 * tp, seed=seed, period=128)
    reqs = synthesize(arr, design.spec.bits_a, design.spec.bits_b,
                      budget=budget, seed=seed + 1)
    rep, _ = design.serve(reqs, replicas=1, autoscaler=scaler, check=True)
    row = _report_row(name, 1.2, budget, rep)
    row["scenario"] = "autoscale_diurnal"
    row["autoscaler"] = scaler.describe()
    return row


def _fused_launch_evidence(name: str, max_batch: int) -> int:
    """Trace (not run) one fused bank round at the largest observed
    round batch: the launch count IS the per-round Pallas launch cost."""
    design = designs.generate(name)
    bucket = 1
    while bucket < max(max_batch, 1):
        bucket <<= 1
    bank = Bank(design.plan, design.spec.bits_a, design.spec.bits_b,
                backend="fused")
    return bank.launch_count(bucket)


def _assert_serving_smoke(sweep, steal_row, scale_row) -> None:
    """The CI serving contract (see module docstring)."""
    rows = sweep + [steal_row, scale_row]
    bad = [(r["design"], r["load_factor"]) for r in rows
           if r["slo_violations"]]
    assert not bad, f"admitted requests missed their SLO on {bad}"
    assert all(r["bit_exact"] for r in rows), \
        "a serving response diverged from the bigint oracle"
    below = [r for r in sweep if r["load_factor"] < 1.0]
    assert below, "smoke sweep has no below-provisioned point"
    bad = [(r["design"], r["load_factor"]) for r in below
           if r["n_refused"]]
    assert not bad, \
        f"refusals below provisioned throughput on {bad}"
    bad = [(r["design"], r["load_factor"]) for r in below
           if r["p99_cycles"] > r["budget_cycles"]]
    assert not bad, f"p99 over the SLO budget below provisioned TP: {bad}"
    above = [r for r in sweep if r["load_factor"] > 1.0]
    assert above, "smoke sweep has no overload point"
    for r in above:
        # graceful degradation: the overloaded bank must keep serving
        # near its provisioned rate (refusing the excess), not collapse
        floor = 0.6 * float(eval_fraction(r["provisioned_tp"]))
        assert r["goodput"] >= floor, \
            (f"goodput collapsed under overload on {r['design']}: "
             f"{r['goodput']:.3f}/cy < {floor:.3f}/cy")
        assert r["n_refused"] > 0, \
            (f"{r['design']} overloaded with no refusals -- admission "
             f"control is not engaging")
    assert steal_row["steals"] > 0, \
        "skewed-router scenario produced no work steals"
    peak = max(n for _, n in scale_row["replica_timeline"])
    assert peak > 1, "diurnal scenario never scaled past 1 replica"
    _row("serving.smoke_gate", 0.0,
         f"zero_viol=True zero_refusals_below_tp=True "
         f"graceful_overload=True steals={steal_row['steals']} "
         f"peak_replicas={peak}")


def eval_fraction(s: str) -> float:
    from fractions import Fraction
    return float(Fraction(s))


def bench_serving(out_path: str | None = None, smoke: bool = False):
    """Serve every (design, load) cell; emit CSV + BENCH_serving.json."""
    loads = SMOKE_LOADS if smoke else FULL_LOADS
    n = N_SMOKE if smoke else N_REQUESTS
    sweep = []
    for name in DESIGN_POINTS:
        for load in loads:
            r = run_sweep_point(name, load, n, seed=17)
            sweep.append(r)
            _row(f"serving.{name}_rho{load}", r["wall_s"] * 1e6,
                 f"offered={r['offered_rate']:.3f}/cy "
                 f"goodput={r['goodput']:.3f}/cy "
                 f"p50={r['p50_cycles']} p99={r['p99_cycles']}cy "
                 f"refused={r['n_refused']} viol={r['slo_violations']} "
                 f"rounds={r['rounds']} exact={r['bit_exact']}")
    steal_row = run_steal_scenario("tp3p5_w32", n, seed=23)
    _row("serving.steal_scenario", steal_row["wall_s"] * 1e6,
         f"steals={steal_row['steals']} "
         f"refused={steal_row['n_refused']} "
         f"viol={steal_row['slo_violations']} "
         f"exact={steal_row['bit_exact']}")
    scale_row = run_autoscale_scenario("tbl8_w32_relaxed", n, seed=29)
    _row("serving.autoscale_scenario", scale_row["wall_s"] * 1e6,
         f"timeline={scale_row['replica_timeline']} "
         f"viol={scale_row['slo_violations']} "
         f"exact={scale_row['bit_exact']}")
    # one fused Pallas launch per bank round: traced, not executed, at
    # the largest round batch the sweep actually produced
    max_batch = max(r["max_round_batch"] for r in sweep)
    launches = _fused_launch_evidence("tp3p5_w32", max_batch)
    _row("serving.fused_round_launches", 0.0,
         f"launches={launches} round_batch<={max_batch}")
    if smoke:
        assert launches == 1, \
            f"a fused bank round traces to {launches} launches, not 1"
        _assert_serving_smoke(sweep, steal_row, scale_row)
    path = out_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump({"fields": FIELDS,
                   "design_points": sweep,
                   "scenarios": [steal_row, scale_row],
                   "fused_launches_per_round": launches,
                   "smoke": smoke}, f, indent=1)
    _row("serving.artifact", 0.0,
         f"wrote={path} n={len(sweep) + 2}")
    return sweep


ALL = [bench_serving]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("out", nargs="?", default=None,
                    help="output JSON path (default: repo-root "
                         "BENCH_serving.json)")
    ap.add_argument("--out", dest="out_flag", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: reduced load grid and request count")
    args = ap.parse_args()
    runtime.enable_compilation_cache()
    print("name,us_per_call,derived")
    bench_serving(args.out_flag or args.out, smoke=args.smoke)

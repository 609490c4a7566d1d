"""``python -m repro.verify``: sweep every generatable design and report.

Sections of the sweep (each contributes to ``VERIFY_report.json``):

  registry        every named design in ``repro.designs.registry``,
                  planned exactly as ``generate()`` plans it;
  vocabulary      every instance architecture the autotuner can emit
                  (star; fb/ff over the CT set; Karatsuba levels x
                  adders; signed variants) at widths 8..128, on both
                  bank substrates (core and fused);
  decompositions  sample fractional TPs decomposed by
                  ``autotune.candidates.enumerate_configs``, every
                  candidate checked for throughput + instance safety;
  fused           bank-level fused-megakernel contracts of every
                  registry plan (super-geometry idle masks, SMEM table
                  consistency, window coverage, scratch domination);
  dataflow        jaxpr-level static proofs of the fused launch every
                  registry plan and vocabulary instance implies, and of
                  the standalone kernels: hazard freedom, window/block
                  bounds, VMEM model/budget, and the static FLOPs/HBM
                  roofline per launch;
  mcim_fold       the standalone per-instance ``mcim_fold`` kernel,
                  once per (schedule, CT) of the vocabulary at each
                  width: interval walk, scratch/out widths and dataflow;
  schedulers      determinism/completeness/makespan contracts of every
                  registered dispatch policy;
  bank            ``Bank.dispatch_fn`` staticness under eval_shape;
  lint            AST jit-safety pass over ``src/repro``.

Exit status 1 when any violation is found (the CI gate).  ``--smoke``
shrinks the width/TP grids for fast pre-merge runs; the full sweep is
the release gate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
from fractions import Fraction

from repro.core.mcim import MCIMConfig

from . import contracts, intervals, lint, verify_instance

FULL_WIDTHS = (8, 16, 24, 32, 48, 64, 96, 128)
SMOKE_WIDTHS = (8, 32, 128)
FULL_TPS = ("1/2", "1/3", "5/6", "11/12", "7/2")
SMOKE_TPS = ("1/2", "5/6")


def _vocabulary():
    """Every instance design the planner/autotuner can emit."""
    from repro.autotune.candidates import CT_SET, KARATSUBA_LEVELS
    vocab = [MCIMConfig(arch="star", ct=1)]
    for ct in CT_SET:
        vocab.append(MCIMConfig(arch="fb", ct=ct))
        vocab.append(MCIMConfig(arch="ff", ct=ct))
    for levels in KARATSUBA_LEVELS:
        for adder in ("1ca", "3ca"):
            vocab.append(MCIMConfig(arch="karatsuba", ct=3,
                                    levels=levels, adder=adder))
    vocab.extend(dataclasses.replace(cfg, signed=True) for cfg in list(vocab))
    return tuple(vocab)


def _cfg_label(cfg: MCIMConfig) -> str:
    parts = [cfg.arch, f"ct={cfg.ct}"]
    if cfg.arch == "karatsuba":
        parts.append(f"K={cfg.levels}")
    if cfg.adder != "1ca":
        parts.append(cfg.adder)
    if cfg.signed:
        parts.append("signed")
    return "(".join([parts[0], ",".join(parts[1:])]) + ")"


def _viol_json(v) -> dict:
    return dataclasses.asdict(v)


def sweep_registry() -> tuple:
    """Plan every registered design the way generate() would, verify."""
    from repro.designs import registry
    from repro.designs.compile import _plan_with_timing
    from . import VerificationError
    results, violations = [], []
    for name in sorted(registry.names()):
        spec = registry.get(name)
        try:
            plan, _ = _plan_with_timing(spec)
        except VerificationError as e:
            violations.extend(e.violations)
            results.append({"design": name, "ok": False,
                            "violations": len(e.violations)})
            continue
        entry = {"design": name, "ok": True,
                 "throughput": str(plan.throughput), "instances": []}
        for count, cfg in plan.configs:
            rep = intervals.analyze(spec.bits_a, spec.bits_b, cfg)
            entry["instances"].append({
                "config": _cfg_label(cfg), "count": count,
                "headroom_bits": rep.headroom_bits,
                "required_width": rep.required_width})
        results.append(entry)
    return results, violations


def sweep_vocabulary(widths) -> tuple:
    results, violations = [], []
    for w in widths:
        for cfg in _vocabulary():
            vs = verify_instance(w, w, cfg)
            violations.extend(vs)
            rep = intervals.analyze(w, w, cfg)
            results.append({
                "bits": w, "config": _cfg_label(cfg),
                "ok": not vs, "headroom_bits": rep.headroom_bits,
                "required_width": rep.required_width})
    return results, violations


def sweep_decompositions(tps, bits: int = 32) -> tuple:
    from repro.designs import DesignSpec
    from repro.autotune.candidates import enumerate_configs
    results, violations = [], []
    for tp in tps:
        spec = DesignSpec(bits, bits, Fraction(tp))
        n_checked = 0
        bad = 0
        for configs in enumerate_configs(spec):
            vs = list(contracts.check_throughput(configs, spec.throughput))
            for _, cfg in configs:
                vs.extend(verify_instance(bits, bits, cfg))
            n_checked += 1
            if vs:
                bad += 1
                violations.extend(vs)
        results.append({"tp": tp, "bits": bits,
                        "candidates": n_checked, "failing": bad})
    return results, violations


def sweep_fused() -> tuple:
    """Fused-megakernel contracts of every registry plan.

    Per design: the bank-level super-geometry promises (idle-step
    masks, SMEM table consistency, shared widths) plus the fused
    window coverage, scratch domination and interval walk of every
    instance -- the proof obligations of running that plan as ONE
    Pallas launch.  (The vocabulary sweep already covers fused
    per-instance checks width-by-width via ``verify_instance``.)
    """
    from repro.designs import registry
    from repro.designs.compile import _plan_with_timing
    from . import VerificationError
    results, violations = [], []
    for name in sorted(registry.names()):
        spec = registry.get(name)
        try:
            plan, _ = _plan_with_timing(spec)
        except VerificationError:
            continue                  # already reported by sweep_registry
        vs = list(contracts.check_fused_plan(spec.bits_a, spec.bits_b,
                                             plan.configs))
        worst = None
        for _, cfg in plan.configs:
            vs.extend(contracts.check_fused_schedule(
                spec.bits_a, spec.bits_b, cfg))
            vs.extend(contracts.check_fused_widths(
                spec.bits_a, spec.bits_b, cfg))
            rep = intervals.analyze(spec.bits_a, spec.bits_b, cfg,
                                    substrate="fused")
            vs.extend(rep.violations)
            if worst is None or rep.headroom_bits < worst:
                worst = rep.headroom_bits
        violations.extend(vs)
        results.append({"design": name, "ok": not vs,
                        "fused_headroom_bits": worst})
    return results, violations


def sweep_dataflow(widths) -> tuple:
    """Static dataflow proofs of every Pallas launch the repo can plan.

    The fused launch of every registry plan and of every autotuner
    vocabulary instance, the standalone kernels, and ragged/prime batch
    shapes through the ``mcim_fold`` tiler.  Per launch: hazard freedom,
    window/block bounds, the VMEM model/budget and the static roofline
    (``arith_intensity``).  Distinct launch geometries are analyzed once
    (cached), so the sweep cost scales with geometry variety, not design
    count.
    """
    from repro.designs import registry
    from repro.designs.compile import _plan_with_timing
    from . import VerificationError, dataflow
    results, violations = [], []

    def plan_entry(bits_a, bits_b, configs):
        reps = dataflow.analyze_plan(bits_a, bits_b, configs)
        vs = [v for rep in reps for v in rep.violations]
        return reps, vs

    for name in sorted(registry.names()):
        spec = registry.get(name)
        try:
            plan, _ = _plan_with_timing(spec)
        except VerificationError:
            continue              # already reported by sweep_registry
        reps, vs = plan_entry(spec.bits_a, spec.bits_b, plan.configs)
        violations.extend(vs)
        results.append({
            "design": name, "ok": not vs,
            "launches": [{
                "launch": r.name, "grid": list(r.grid),
                "flops": r.flops, "hbm_bytes": r.hbm_bytes,
                "arith_intensity": round(r.arith_intensity, 4),
                "vmem_total_bytes": r.vmem.get("total_bytes"),
                "ok": r.ok} for r in reps]})

    for w in widths:
        for cfg in _vocabulary():
            reps, vs = plan_entry(w, w, ((1, cfg),))
            violations.extend(vs)
            results.append({"bits": w, "config": _cfg_label(cfg),
                            "ok": not vs,
                            "launches": [r.name for r in reps]})

    for rep in dataflow.analyze_standalone():
        violations.extend(rep.violations)
        results.append({
            "launch": rep.name, "grid": list(rep.grid),
            "flops": rep.flops,
            "arith_intensity": round(rep.arith_intensity, 4),
            "ok": rep.ok})

    for batch, rep in zip(dataflow.RAGGED_BATCHES,
                          dataflow.analyze_tiling()):
        violations.extend(rep.violations)
        results.append({"launch": rep.name, "batch": batch,
                        "grid": list(rep.grid), "ok": rep.ok})
    return results, violations


def sweep_mcim_fold(widths) -> tuple:
    """The standalone ``mcim_fold`` kernel (``big_mul``), which no bank
    backend launches: once per (schedule, CT) of the unsigned vocabulary
    at each width, its interval walk, its scratch/out widths against
    that walk, and the dataflow proofs of its launch."""
    from . import dataflow
    results, violations = [], []
    for w in widths:
        seen = set()
        for cfg in _vocabulary():
            key = dataflow._instance_params(cfg)
            if cfg.signed or key in seen:
                continue
            seen.add(key)
            vs = list(intervals.analyze(w, w, cfg,
                                        substrate="kernel").violations)
            vs.extend(contracts.check_widths(w, w, cfg))
            [rep] = dataflow.analyze_plan(w, w, ((1, cfg),),
                                          substrate="kernel")
            vs.extend(rep.violations)
            violations.extend(vs)
            results.append({"bits": w, "launch": rep.name, "ok": not vs})
    return results, violations


def sweep_bank(bits: int = 32) -> tuple:
    from repro.core import planner
    violations = []
    for tp in (Fraction(7, 2), Fraction(5, 6)):
        plan = planner.plan_throughput(bits, bits, tp)
        violations.extend(contracts.check_bank_static(plan, bits, bits))
    return ([{"checked_plans": 2, "ok": not violations}], violations)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.verify",
        description="statically verify every generatable design")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced width/TP grids (the pre-merge CI gate)")
    ap.add_argument("--out", default="VERIFY_report.json",
                    help="report path (default: %(default)s)")
    args = ap.parse_args(argv)

    widths = SMOKE_WIDTHS if args.smoke else FULL_WIDTHS
    tps = SMOKE_TPS if args.smoke else FULL_TPS

    sections, all_violations = {}, []

    print(f"repro.verify sweep ({'smoke' if args.smoke else 'full'}): "
          f"widths {widths}, TPs {tps}")

    sections["registry"], vs = sweep_registry()
    all_violations.extend(vs)
    print(f"  registry:       {len(sections['registry'])} designs, "
          f"{len(vs)} violations")

    sections["vocabulary"], vs = sweep_vocabulary(widths)
    all_violations.extend(vs)
    print(f"  vocabulary:     {len(sections['vocabulary'])} design "
          f"points, {len(vs)} violations")

    sections["decompositions"], vs = sweep_decompositions(tps)
    all_violations.extend(vs)
    n_cand = sum(r["candidates"] for r in sections["decompositions"])
    print(f"  decompositions: {n_cand} candidates, {len(vs)} violations")

    sections["fused"], vs = sweep_fused()
    all_violations.extend(vs)
    print(f"  fused:          {len(sections['fused'])} plans as one "
          f"launch, {len(vs)} violations")

    sections["dataflow"], vs = sweep_dataflow(widths)
    all_violations.extend(vs)
    print(f"  dataflow:       {len(sections['dataflow'])} launch "
          f"points, {len(vs)} violations")

    sections["mcim_fold"], vs = sweep_mcim_fold(widths)
    all_violations.extend(vs)
    print(f"  mcim_fold:      {len(sections['mcim_fold'])} standalone "
          f"launches, {len(vs)} violations")

    # the serving package registers its slo_edf policy at import: pull
    # it in before the sweep so an unverifiable serving scheduler fails
    # CI here (and is therefore unplannable)
    import repro.serving  # noqa: F401
    from repro.core.bank.schedule import SCHEDULERS
    vs = contracts.check_all_schedulers()
    sections["schedulers"] = [{"cases": len(contracts.SCHEDULER_CASES),
                               "policies": sorted(SCHEDULERS),
                               "ok": not vs}]
    all_violations.extend(vs)
    print(f"  schedulers:     {len(contracts.SCHEDULER_CASES)} cases x "
          f"{len(SCHEDULERS)} policies, {len(vs)} violations")

    sections["bank"], vs = sweep_bank()
    all_violations.extend(vs)
    print(f"  bank statics:   {len(vs)} violations")

    import repro
    src_root = pathlib.Path(repro.__file__).parent
    vs = lint.lint_tree(src_root)
    sections["lint"] = [{"root": str(src_root), "ok": not vs}]
    all_violations.extend(vs)
    print(f"  lint:           {src_root}, {len(vs)} violations")

    report = {
        "smoke": args.smoke,
        "widths": list(widths),
        "summary": {
            "sections": {k: len(v) for k, v in sections.items()},
            "violations": len(all_violations),
            "ok": not all_violations,
        },
        "violations": [_viol_json(v) for v in all_violations],
        **sections,
    }
    out_path = pathlib.Path(args.out)
    out_path.write_text(json.dumps(report, indent=2, sort_keys=True))
    print(f"report: {out_path}")

    if all_violations:
        print(f"FAIL: {len(all_violations)} violation(s)")
        for v in all_violations[:20]:
            print(f"  {v.describe()}")
        if len(all_violations) > 20:
            print(f"  ... and {len(all_violations) - 20} more")
        return 1
    print("OK: every design proved overflow-safe and contract-conformant")
    return 0


if __name__ == "__main__":
    sys.exit(main())

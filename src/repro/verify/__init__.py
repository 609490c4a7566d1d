"""repro.verify: static design verification -- no execution required.

Four analyzers prove properties of every design the repo can generate:

  * :mod:`.intervals`  -- abstract interpretation of the limb pipeline:
    every uint32 carry-save column provably stays below 2**32, for the
    exact dataflow of each architecture on each substrate;
  * :mod:`.contracts`  -- schedule contracts: partial-product coverage
    (each a_i*b_j exactly once, Karatsuba combine as a polynomial
    identity), kernel scratch/out widths vs the proven requirement,
    Plan throughput sums, scheduler determinism/completeness, bank
    dispatch staticness under ``jax.eval_shape``;
  * :mod:`.dataflow`   -- jaxpr-level abstract interpretation of every
    Pallas launch a plan implies (with :mod:`.vmem`): hazard freedom
    over scratch/output refs, BlockSpec/window bounds, the per-step
    VMEM model and budget, and a static FLOPs/HBM-bytes roofline;
  * :mod:`.lint`       -- AST taint pass over the source tree flagging
    Python control flow on traced values, non-static scheduler state
    and interpret-mode environment reads outside the runtime shim.

``python -m repro.verify`` sweeps the full design registry plus the
autotuner's enumeration vocabulary and writes ``VERIFY_report.json``
(CI gates on its exit status).  ``designs.generate`` and
``autotune.search`` call :func:`assert_plan` at plan time, so a design
that cannot be proven safe errors before it ever executes.
"""
from __future__ import annotations

import functools

from . import intervals, contracts, lint
from .intervals import IntervalReport, Violation, analyze
from .contracts import (check_coverage, check_widths, check_throughput,
                        check_fused_schedule, check_fused_widths,
                        check_fused_plan, check_all_schedulers,
                        check_bank_static)
from .lint import lint_tree, lint_source

__all__ = [
    "intervals", "contracts", "lint", "dataflow", "vmem",
    "IntervalReport", "Violation", "VerificationError", "DataflowError",
    "analyze", "check_coverage", "check_widths", "check_throughput",
    "check_fused_schedule", "check_fused_widths", "check_fused_plan",
    "check_all_schedulers", "check_bank_static",
    "lint_tree", "lint_source",
    "verify_instance", "verify_plan", "assert_plan", "verify_design",
    "verify_plan_dataflow", "assert_plan_dataflow",
]

#: substrates swept per instance: the two the bank can run (fused
#: handles signedness through the bank-wide correction pass)
_SUBSTRATES = ("core", "fused")


class VerificationError(ValueError):
    """A design the static analyzers cannot prove safe.

    Raised by :func:`assert_plan` at plan time: the design never
    executes.  ``violations`` carries the structured findings.
    """

    def __init__(self, violations):
        self.violations = tuple(violations)
        lines = [v.describe() for v in self.violations]
        super().__init__(
            f"{len(lines)} verification violation(s):\n  " +
            "\n  ".join(lines))


class DataflowError(VerificationError):
    """A Pallas launch the dataflow analyzer cannot prove safe.

    Raised by :func:`assert_plan_dataflow`: a hazard, bounds, VMEM or
    window-table finding on the launches a plan implies.
    """


# after DataflowError: dataflow imports the class at raise time
from . import dataflow, vmem                        # noqa: E402
from .dataflow import verify_plan_dataflow          # noqa: E402


@functools.lru_cache(maxsize=4096)
def verify_instance(bits_a: int, bits_b: int, cfg) -> tuple:
    """All violations of one MCIMConfig at the given widths.

    Cached (MCIMConfig is frozen/hashable) so plan-time gating in
    ``generate()``/``search()`` costs one analysis per distinct design
    point per process, not one per call.
    """
    out = []
    out.extend(contracts.check_coverage(bits_a, bits_b, cfg))
    out.extend(contracts.check_fused_schedule(bits_a, bits_b, cfg))
    out.extend(contracts.check_fused_widths(bits_a, bits_b, cfg))
    for sub in _SUBSTRATES:
        out.extend(intervals.analyze(bits_a, bits_b, cfg,
                                     substrate=sub).violations)
    return tuple(out)


def verify_plan(bits_a: int, bits_b: int, configs,
                throughput=None) -> tuple:
    """All violations of a plan: throughput sum + every instance + the
    fused super-geometry (idle-step masks, SMEM table consistency)."""
    out = []
    configs = tuple(configs)
    if throughput is not None:
        out.extend(contracts.check_throughput(configs, throughput))
    for _, cfg in configs:
        out.extend(verify_instance(bits_a, bits_b, cfg))
    out.extend(contracts.check_fused_plan(bits_a, bits_b, configs))
    return tuple(out)


def assert_plan(bits_a: int, bits_b: int, configs,
                throughput=None) -> None:
    """Raise :class:`VerificationError` unless the plan proves safe.

    The plan-time gate ``designs.generate`` / ``designs.compile_plan``
    and ``autotune.search`` run on every candidate before compiling or
    scoring it.
    """
    violations = verify_plan(bits_a, bits_b, configs, throughput)
    if violations:
        raise VerificationError(violations)


def assert_plan_dataflow(bits_a: int, bits_b: int, configs,
                         budget=None) -> None:
    """Raise :class:`DataflowError` unless every launch proves safe.

    The fourth plan-time gate: traces (never executes) the one fused
    Pallas launch the bank issues for the plan and rejects hazards,
    out-of-bounds windows/block indices and VMEM model/budget breaks.
    Results are cached per distinct launch geometry inside
    :mod:`.dataflow`, so repeated gating is cheap.
    """
    violations = dataflow.verify_plan_dataflow(bits_a, bits_b,
                                               tuple(configs),
                                               budget=budget)
    if violations:
        raise DataflowError(violations)


def verify_design(design) -> tuple:
    """All violations of a ``CompiledDesign`` (post-hoc checking)."""
    return verify_plan(design.spec.bits_a, design.spec.bits_b,
                       design.plan.configs, design.plan.throughput)

"""generate(): compile a DesignSpec into an executable CompiledDesign.

This is the paper's design generator as one function.  Planning is no
longer area-only: candidate plans are filtered through
``core.timing_model`` so the clock-period / fmax customization is wired
into design selection (a relaxed plan whose feedback-loop instances
cannot meet ``spec.clock_ns`` falls back to pipelineable designs, and a
latency budget rejects designs whose pipeline depth at the target
exceeds it).  The resulting ``CompiledDesign`` owns the whole pipeline:
the chosen ``planner.Plan``, an executable ``bank.Bank`` (scheduler and
backend resolved from the spec), optional mesh replication, and the
area/latency/fmax properties the paper's tables report.
"""
from __future__ import annotations

import dataclasses
import math
from fractions import Fraction

import numpy as np
import jax

from repro.core import limbs as L
from repro.core import planner, timing_model
from repro.core.bank import Bank, BankReport, StreamingScheduler, \
    sharded_execute
from repro.core.mcim import MCIMConfig
from repro.core import area_model
from repro.core import power_model
from repro import verify
from repro.spans import span

from .spec import DesignSpec, DesignError, TimingError, LatencyError


def _timing_bits(spec: DesignSpec) -> int:
    """Width driving the critical path (the wider operand dominates)."""
    return max(spec.bits_a, spec.bits_b)


def _timing_violations(plan: planner.Plan, bits: int,
                       clock_ns: float) -> list:
    return [cfg for _, cfg in plan.configs
            if not timing_model.meets_timing(cfg.arch, bits, clock_ns,
                                             cfg.adder)]


def _instance_latency(cfg: MCIMConfig, bits: int,
                      clock_ns: float | None) -> int:
    t = clock_ns if clock_ns is not None else math.inf
    return timing_model.latency_at(cfg.arch, bits, t, cfg.ct)


def _instance_period(cfg: MCIMConfig, bits: int,
                     clock_ns: float | None) -> float:
    """Achievable clock period of one instance.

    Non-pipelineable instances are capped at their combinational path;
    pipelineable ones retime down to the requested target (paying
    latency), or run at their natural path when the spec is relaxed.
    """
    t = timing_model.t_comb(cfg.arch, bits)
    if clock_ns is not None and clock_ns < t and \
            timing_model.pipelineable(cfg.arch, cfg.adder):
        return clock_ns
    return t


class CompiledDesign:
    """An executable multiplier design compiled from a :class:`DesignSpec`.

    One object owns the whole pipeline the call sites used to hand-wire:
    the timing-filtered ``plan``, the executable ``bank`` (scheduler +
    backend resolved), optional mesh replication, the paper's
    area / latency / fmax figures as properties, and full provenance
    (``spec`` / ``to_json``).  ``mul(a, b)`` multiplies limb arrays --
    or plain Python ints -- bit-exactly through whichever substrate the
    spec selected.
    """

    def __init__(self, spec: DesignSpec, plan: planner.Plan, bank: Bank,
                 mesh=None, timing_fallback: bool = False):
        self.spec = spec
        self.plan = plan
        self.bank = bank
        self.mesh = mesh
        #: True when the relaxed plan missed spec.clock_ns and planning
        #: was redone with strict (pipelineable-only) candidates.
        self.timing_fallback = timing_fallback
        self.la = bank.la
        self.lb = bank.lb

    # ------------------------------------------------------------ execute
    def mul(self, a, b):
        """Multiply: limb arrays (B, LA) x (B, LB) -> (B, LA+LB), or two
        Python ints -> int (two's-complement when the spec is signed).

        Routes to the replicated sharded engine when the spec asked for
        replicas, else to the single bank's jitted dispatch.
        """
        with span("mul"):
            if isinstance(a, (int, np.integer)) and \
                    isinstance(b, (int, np.integer)):
                return self._mul_ints(int(a), int(b))
            if self.mesh is not None:
                return sharded_execute(self.plan, a, b, self.mesh,
                                       self.spec.mesh_axis,
                                       backend=self.bank.backend,
                                       scheduler=self.spec.scheduler)
            return self.bank.execute(a, b)

    def _mul_ints(self, a: int, b: int) -> int:
        enc_a = self._encode(a, self.spec.bits_a, self.la)
        enc_b = self._encode(b, self.spec.bits_b, self.lb)
        import jax.numpy as jnp
        out = self.bank.execute(jnp.asarray(enc_a)[None],
                                jnp.asarray(enc_b)[None])[0]
        total = L.from_limbs(np.asarray(out))
        if self.spec.signed:
            width = L.RADIX_BITS * (self.la + self.lb)
            if total >= 1 << (width - 1):
                total -= 1 << width
        return total

    def _encode(self, v: int, bits: int, limbs: int) -> np.ndarray:
        if self.spec.signed:
            if not -(1 << (bits - 1)) <= v < (1 << (bits - 1)):
                raise ValueError(f"{v} out of signed {bits}-bit range")
            v %= 1 << (L.RADIX_BITS * limbs)
        elif not 0 <= v < (1 << bits):
            raise ValueError(f"{v} out of unsigned {bits}-bit range")
        return L.to_limbs(v, limbs)

    # ------------------------------------------------------------ reports
    def report(self, batch: int) -> BankReport:
        """Cycle accounting for one batch (per replica when sharded),
        with the design's modeled energy/op and peak power attached."""
        if self.spec.replicas > 1:
            if batch % self.spec.replicas:
                raise ValueError(f"batch {batch} does not divide over "
                                 f"{self.spec.replicas} replicas")
            batch //= self.spec.replicas
        return dataclasses.replace(self.bank.report(batch),
                                   energy_per_op_pj=self.energy_per_op_pj,
                                   peak_power_mw=self.peak_power_mw)

    def replay(self, arrivals) -> BankReport:
        """Replay an arrival trace (e.g. ``ServeEngine.arrival_trace()``)
        through this design's bank under the streaming scheduler: one
        work item per trace entry, issued no earlier than its arrival
        cycle."""
        trace = tuple(int(c) for c in arrivals)
        sched = StreamingScheduler(arrivals=trace)
        return self.bank.report(len(trace), scheduler=sched)

    def serve(self, requests, *, replicas: int = 1,
              round_cycles: int | None = None, steal: bool = True,
              autoscaler=None, check: bool = False):
        """Serve a request stream *online* through this design.

        Where :meth:`replay` scores a finished arrival trace,
        ``serve`` runs the full event loop of
        :class:`repro.serving.Worker`: SLO admission control, EDF
        dispatch in bank rounds (one fused Pallas launch per round on
        the fused backend), work stealing across ``replicas``
        independent bank replicas, and optional autoscaling (pass a
        ``repro.serving.Autoscaler``).  ``check=True`` verifies every
        response against the Python-bigint oracle.

        Returns ``(report, responses)``: the
        :class:`~repro.serving.ServingReport` and the per-request
        ``{rid: Response}`` outcomes.
        """
        from repro.serving import Worker
        worker = Worker(self, replicas=replicas, round_cycles=round_cycles,
                        steal=steal, autoscaler=autoscaler, check=check)
        report = worker.run(requests)
        return report, worker.responses

    # --------------------------------------------------------- properties
    @property
    def throughput(self):
        """Aggregate multiplications/cycle (replicas x per-bank TP)."""
        return self.plan.throughput * self.spec.replicas

    @property
    def area(self) -> float:
        """Modeled silicon area (um^2), all replicas, including the
        synthesis stress of meeting ``spec.clock_ns`` when set."""
        bits = _timing_bits(self.spec)
        total = 0.0
        for count, cfg in self.plan.configs:
            a = area_model.area_um2(self.spec.bits_a, self.spec.bits_b, cfg)
            if self.spec.clock_ns is not None:
                a *= timing_model.stress(cfg.arch, bits, self.spec.clock_ns)
            total += count * a
        return total * self.spec.replicas

    @property
    def latency_cycles(self) -> int:
        """Cycles from issue to retire for one multiplication: the worst
        instance's CT plus any retiming stages the clock target forces."""
        bits = _timing_bits(self.spec)
        return max(_instance_latency(cfg, bits, self.spec.clock_ns)
                   for _, cfg in self.plan.configs)

    @property
    def fmax_estimate(self) -> float:
        """Achievable clock (GHz): the slowest instance's period, with
        pipelineable instances retimed down to the spec's target."""
        bits = _timing_bits(self.spec)
        period = max(_instance_period(cfg, bits, self.spec.clock_ns)
                     for _, cfg in self.plan.configs)
        return 1.0 / period

    @property
    def _stress(self) -> float:
        """Synthesis-stress multiplier of the spec's clock target (1.0
        when relaxed): tight clocks force larger, higher-capacitance
        cells, inflating area AND switched energy alike."""
        if self.spec.clock_ns is None:
            return 1.0
        return timing_model.stress("star", _timing_bits(self.spec),
                                   self.spec.clock_ns)

    @property
    def energy_per_op_pj(self) -> float:
        """Modeled energy per multiplication (pJ), throughput-weighted
        over the bank's instances, including synthesis stress."""
        return power_model.plan_energy_per_op_pj(
            self.spec.bits_a, self.spec.bits_b, self.plan.configs,
            stress=self._stress)

    @property
    def peak_power_mw(self) -> float:
        """Modeled peak power (mW, all replicas): worst-cycle switched
        capacitance of every instance together, at the spec's clock (or
        the slowest instance's natural period when relaxed)."""
        period = 1.0 / self.fmax_estimate
        return power_model.plan_peak_power_mw(
            self.spec.bits_a, self.spec.bits_b, self.plan.configs,
            clock_ns=period, stress=self._stress) * self.spec.replicas

    def describe(self) -> str:
        extra = " timing_fallback" if self.timing_fallback else ""
        return (f"CompiledDesign[{self.spec.describe()} -> "
                f"{self.plan.describe()}  "
                f"energy={self.energy_per_op_pj:.2f}pJ/op  "
                f"peak={self.peak_power_mw:.2f}mW  "
                f"backend={self.bank.backend}  "
                f"scheduler={self.bank.scheduler.name}{extra}]")

    # --------------------------------------------------------- provenance
    def to_json(self) -> str:
        """The spec's lossless JSON: compiling it again reproduces this
        design bit-exactly (see DesignSpec.from_json)."""
        return self.spec.to_json()


# ---------------------------------------------------------------- generate

def _resolve_backend(spec: DesignSpec) -> str:
    """The spec's backend; "auto" is the fused megakernel where Pallas
    is native, pure-jnp elsewhere (the CPU container would pay
    interpret-mode kernel cost for nothing)."""
    if spec.backend != "auto":
        return spec.backend
    return "fused" if jax.default_backend() == "tpu" else "core"


def _achieved_throughput(plan: planner.Plan):
    return sum(Fraction(count, cfg.ct) for count, cfg in plan.configs)


def _plan_with_timing(spec: DesignSpec):
    plan = planner.plan_throughput(spec.bits_a, spec.bits_b,
                                   spec.throughput,
                                   strict_timing=spec.strict_timing,
                                   objective=spec.objective)
    if _achieved_throughput(plan) != spec.throughput:
        # plan_throughput silently drops the residual when a fractional
        # TP cannot be decomposed over its CT set; the facade's contract
        # is that the compiled design sustains exactly what was asked
        raise DesignError(
            f"throughput {spec.throughput} is not decomposable over the "
            f"planner's CT combinations (best plan sums to "
            f"{_achieved_throughput(plan)}); pick a TP whose fractional "
            f"part is a sum of 1/ct for ct in (2, 3, 4, 6, 8, 12)")
    fallback = False
    bits = _timing_bits(spec)
    if spec.clock_ns is not None:
        bad = _timing_violations(plan, bits, spec.clock_ns)
        if bad and not spec.strict_timing:
            # relaxed winner misses the clock: re-plan over pipelineable
            # candidates only (the paper's strict-timing tables)
            plan = planner.plan_throughput(spec.bits_a, spec.bits_b,
                                           spec.throughput,
                                           strict_timing=True,
                                           objective=spec.objective)
            fallback = True
            bad = _timing_violations(plan, bits, spec.clock_ns)
        if bad:
            worst = max(timing_model.t_comb(cfg.arch, bits) for cfg in bad)
            raise TimingError(
                f"no design meets clock {spec.clock_ns} ns for "
                f"{spec.describe()}: {[cfg.arch for cfg in bad]} bottom "
                f"out at t_comb={worst:.2f} ns and cannot pipeline")
    if spec.latency_budget is not None:
        lat = max(_instance_latency(cfg, bits, spec.clock_ns)
                  for _, cfg in plan.configs)
        if lat > spec.latency_budget:
            raise LatencyError(
                f"{spec.describe()} needs {lat} cycles of latency at "
                f"clock={spec.clock_ns} ns, over the budget of "
                f"{spec.latency_budget}")
    if spec.signed:
        plan = dataclasses.replace(plan, configs=tuple(
            (count, dataclasses.replace(cfg, signed=True))
            for count, cfg in plan.configs))
    # static verification gate: a plan the interval/contract analyzers
    # cannot prove overflow-safe and schedule-conformant never compiles
    verify.assert_plan(spec.bits_a, spec.bits_b, plan.configs,
                       plan.throughput)
    # dataflow gate: the fused Pallas launch the plan implies must prove
    # hazard-free, in-bounds and within its VMEM model -- without
    # executing (cached per distinct launch geometry)
    verify.assert_plan_dataflow(spec.bits_a, spec.bits_b, plan.configs)
    return plan, fallback


def _resolve_mesh(spec: DesignSpec, mesh):
    if spec.replicas == 1:
        return None
    if mesh is not None:
        if spec.mesh_axis not in mesh.shape:
            raise DesignError(f"mesh has no axis {spec.mesh_axis!r}")
        if mesh.shape[spec.mesh_axis] != spec.replicas:
            raise DesignError(
                f"mesh axis {spec.mesh_axis!r} has "
                f"{mesh.shape[spec.mesh_axis]} devices, spec wants "
                f"{spec.replicas} replicas")
        return mesh
    devices = jax.devices()
    if len(devices) < spec.replicas:
        raise DesignError(
            f"{spec.replicas} replicas need {spec.replicas} devices, "
            f"only {len(devices)} available (pass an explicit mesh or "
            f"lower spec.replicas)")
    return jax.sharding.Mesh(np.asarray(devices[:spec.replicas]),
                             (spec.mesh_axis,))


def generate(spec: DesignSpec, mesh=None) -> CompiledDesign:
    """Compile ``spec`` into an executable :class:`CompiledDesign`.

    The single front door for the repo: planner selection filtered by
    the timing model (clock + latency customization), scheduler/backend
    resolution, bank construction and optional mesh replication all
    happen here.  ``mesh`` may supply an existing device mesh for
    ``spec.replicas > 1``; otherwise one is built over the first
    ``replicas`` devices.
    """
    if isinstance(spec, str):
        from .registry import get
        spec = get(spec)
    plan, fallback = _plan_with_timing(spec)
    backend = _resolve_backend(spec)
    bank = Bank(plan, spec.bits_a, spec.bits_b, backend=backend,
                scheduler=spec.scheduler)
    return CompiledDesign(spec, plan, bank,
                          mesh=_resolve_mesh(spec, mesh),
                          timing_fallback=fallback)


def compile_plan(spec: DesignSpec, configs, mesh=None) -> CompiledDesign:
    """Compile ``spec`` with an EXPLICIT instance list, bypassing the
    planner's pick-one policy.

    This is the autotuner's compile path: ``repro.autotune`` enumerates
    candidate decompositions itself and materializes any point off its
    Pareto front through here.  ``configs`` is an iterable of
    ``(count, MCIMConfig)``; it must sum to exactly ``spec.throughput``
    and every instance must meet the spec's clock/latency constraints
    (the same gate ``generate`` applies, not a duplicate of it).
    """
    configs = tuple((int(count), cfg) for count, cfg in configs)
    if spec.signed:
        configs = tuple((count, dataclasses.replace(cfg, signed=True))
                        for count, cfg in configs)
    area = sum(count * area_model.area_um2(spec.bits_a, spec.bits_b, cfg)
               for count, cfg in configs)
    plan = planner.Plan(configs=configs, throughput=spec.throughput,
                        area=area)
    if _achieved_throughput(plan) != spec.throughput:
        raise DesignError(
            f"explicit configs sum to TP={_achieved_throughput(plan)}, "
            f"spec wants {spec.throughput}")
    bits = _timing_bits(spec)
    if spec.strict_timing:
        bad = [cfg for _, cfg in configs
               if not timing_model.pipelineable(cfg.arch, cfg.adder)]
        if bad:
            raise TimingError(f"strict spec given non-pipelineable "
                              f"instances: {[cfg.arch for cfg in bad]}")
    if spec.clock_ns is not None:
        bad = _timing_violations(plan, bits, spec.clock_ns)
        if bad:
            raise TimingError(
                f"explicit configs miss clock {spec.clock_ns} ns: "
                f"{[cfg.arch for cfg in bad]}")
    if spec.latency_budget is not None:
        lat = max(_instance_latency(cfg, bits, spec.clock_ns)
                  for _, cfg in configs)
        if lat > spec.latency_budget:
            raise LatencyError(f"explicit configs need {lat} cycles, "
                               f"over the budget of {spec.latency_budget}")
    # same static gates generate() applies: explicit instance lists must
    # prove safe before a bank is built around them
    verify.assert_plan(spec.bits_a, spec.bits_b, plan.configs,
                       plan.throughput)
    verify.assert_plan_dataflow(spec.bits_a, spec.bits_b, plan.configs)
    backend = _resolve_backend(spec)
    bank = Bank(plan, spec.bits_a, spec.bits_b, backend=backend,
                scheduler=spec.scheduler)
    return CompiledDesign(spec, plan, bank,
                          mesh=_resolve_mesh(spec, mesh))

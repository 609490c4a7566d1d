"""Bank execution engine: run ``planner.Plan`` objects as real multipliers.

``planner.plan_throughput`` picks a *bank* of multiplier instances (e.g.
TP=3.5 -> three Star + one CT=2 MCIM).  This module makes plans
executable: a batch of multiplications is dispatched across the plan's
instances by a pluggable :mod:`.schedule` policy exactly the way the
paper's Sec. V-E use case issues work to the silicon bank.

The resulting engine is

  * bit-exact on either of its two backends: ``"core"`` runs each
    instance's pure-jnp ``mcim_mul`` (the CPU path and the reference),
    ``"fused"`` runs the whole bank round as ONE ``kernels.bank_fold``
    Pallas launch (the device path); both equal the Python-int oracle
    regardless of policy;
  * cycle-accounted: the dispatch schedule is simulated once per batch
    size, and ``execute`` keeps its report beside the compiled dispatch,
    giving per-instance busy cycles and the bank makespan, so measured
    throughput can be checked against ``Plan.throughput``;
  * jit/pjit-compatible: the schedule is static for a given batch size,
    so ``execute`` lowers to static indexing + batched multiplies (the
    fused backend gives each instance a contiguous run of static slices,
    the core backend gathers and scatters by constant indices), and
    :mod:`.sharded` can replicate it across a mesh axis.
"""
from __future__ import annotations

import dataclasses
import functools
from fractions import Fraction

import numpy as np
import jax
import jax.numpy as jnp

from .. import limbs as L
from ..mcim import MCIMConfig, mcim_mul
from ..planner import Plan
from repro.spans import span
from .schedule import (completion_cycles, get_scheduler,
                       histogram_percentile, latency_histogram)


#: the bank's execution substrates: pure-jnp per instance, or the whole
#: round as one fused Pallas launch
BACKENDS = ("core", "fused")


def _working_set(backend: str, instances: tuple, la: int, lb: int,
                 tile_b: int) -> int:
    """Per-step VMEM working set of the bank (the TPU analogue of the
    paper's silicon area).

    Core: the sum over instances of each one's ``mcim_fold`` footprint
    (the design's own datapaths side by side).  Fused: the instances
    time-share ONE windowed-schoolbook datapath whose footprint does not
    depend on the instance, so the bank's figure is that one footprint.
    """
    if backend == "fused":
        from repro.kernels.bank_fold import vmem_bytes_per_step
        return vmem_bytes_per_step(la, lb, tile_b)
    from repro.kernels.mcim_fold import vmem_bytes_per_step
    total = 0
    for cfg in instances:
        if cfg.arch == "star":
            total += vmem_bytes_per_step(la, lb, 1, tile_b)
        elif cfg.arch in ("ff", "karatsuba"):
            total += vmem_bytes_per_step(la, lb, cfg.ct, tile_b,
                                         schedule=cfg.arch)
        else:
            total += vmem_bytes_per_step(la, lb, cfg.ct, tile_b)
    return total


# ------------------------------------------------------------------ reports

@dataclasses.dataclass(frozen=True)
class InstanceReport:
    """Per-instance cycle accounting for one executed batch."""
    config: MCIMConfig
    n_ops: int
    busy_cycles: int          # n_ops * ct: cycles the datapath is occupied

    @property
    def ct(self) -> int:
        return self.config.ct


@dataclasses.dataclass(frozen=True)
class BankReport:
    """Throughput accounting for one executed batch."""
    batch: int
    cycles: int                       # bank makespan
    instances: tuple                  # tuple[InstanceReport]
    plan_throughput: Fraction
    working_set_bytes: int            # per-step VMEM footprint of the bank
    scheduler: str = "round_robin"    # policy that produced the makespan
    #: per-request latency histogram, sorted ((cycles, count), ...):
    #: admission (the policy's arrival trace, cycle 0 for batch
    #: policies) to completion -- the same accounting path the online
    #: serving layer reports p50/p99 from
    latency_hist: tuple = ()
    # filled in by CompiledDesign.report() (the bank itself has no spec,
    # so no clock/stress context to model power with)
    energy_per_op_pj: float | None = None
    peak_power_mw: float | None = None

    @property
    def measured_throughput(self) -> Fraction:
        return Fraction(self.batch, self.cycles) if self.cycles else Fraction(0)

    @property
    def utilization(self) -> float:
        if not self.cycles:
            return 0.0
        return float(self.measured_throughput / self.plan_throughput)

    @property
    def energy_pj(self) -> float | None:
        """Total modeled switching energy of the batch."""
        if self.energy_per_op_pj is None:
            return None
        return self.batch * self.energy_per_op_pj

    def latency_percentile(self, q: float):
        """Latency (cycles) at quantile ``q`` of the per-request
        histogram; None for an empty batch."""
        return histogram_percentile(self.latency_hist, q)

    @property
    def latency_p50(self):
        return self.latency_percentile(0.50)

    @property
    def latency_p99(self):
        return self.latency_percentile(0.99)


# ------------------------------------------------------------------ the bank

class Bank:
    """Executable multiplier bank for one ``planner.Plan``.

    ``execute(a, b)`` multiplies a batch of limb vectors
    (B, LA) x (B, LB) -> (B, LA+LB) bit-exactly; ``last_report`` /
    ``report(batch)`` exposes the cycle accounting.  ``backend`` picks
    the substrate ("core": pure-jnp ``mcim_mul`` per instance; "fused":
    the whole bank round as ONE ``kernels.bank_fold`` megakernel
    launch), ``scheduler`` the dispatch policy ("round_robin" |
    "greedy" | "streaming" or any registered
    :class:`~.schedule.Scheduler`).  :meth:`launch_count` reads the
    launches of one round from the traced jaxpr.
    """

    # each distinct batch size compiles its own dispatch; bound the set
    # (FIFO eviction) so ragged serving batches cannot grow it unboundedly
    MAX_COMPILED = 32

    def __init__(self, plan: Plan, bits_a: int, bits_b: int, *,
                 backend: str = "core", scheduler="round_robin",
                 tile_b: int = 256):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}")
        self.plan = plan
        self.bits_a, self.bits_b = bits_a, bits_b
        self.la = L.n_limbs_for_bits(bits_a)
        self.lb = L.n_limbs_for_bits(bits_b)
        self.backend = backend
        self.scheduler = get_scheduler(scheduler)
        self.tile_b = tile_b
        # expand [(count, cfg)] -> flat instance list, Stars first so the
        # fast units drain the head of the queue like the paper's bank
        self.instances = tuple(
            cfg for count, cfg in plan.configs for _ in range(count))
        if not self.instances:
            raise ValueError("plan has no instances")
        self._cts = tuple(cfg.ct for cfg in self.instances)
        if backend == "core":
            self._muls = tuple(functools.partial(mcim_mul, config=cfg)
                               for cfg in self.instances)
        signedness = {cfg.signed for cfg in self.instances}
        if backend == "fused" and len(signedness) > 1:
            raise ValueError(
                "fused backend needs uniform signedness across instances "
                "(the correction pass is applied bank-wide)")
        self._signed = self.instances[0].signed
        # batch size -> (jitted execute, args of its launch span, report)
        self._compiled = {}
        self.last_report = None

    # -------------------------------------------------------------- reports
    def report(self, batch: int, scheduler=None) -> BankReport:
        """Cycle accounting for one batch.  ``scheduler`` overrides the
        bank's policy for this report only (e.g. a StreamingScheduler
        carrying a recorded arrival trace) without recompiling dispatch."""
        with span("bank.report"):
            sched = self.scheduler if scheduler is None else \
                get_scheduler(scheduler)
            assign, cycles = sched.schedule(self._cts, batch)
            return self._report(sched, batch, assign, cycles)

    def _report(self, sched, batch: int, assign: tuple,
                cycles: int) -> BankReport:
        """The report of ``batch`` ops dispatched by ``sched`` as
        ``assign``, retiring the last on cycle ``cycles``."""
        insts = tuple(
            InstanceReport(cfg, len(ops), len(ops) * cfg.ct)
            for cfg, ops in zip(self.instances, assign))
        # per-request latency: completion minus admission, where
        # admission is the policy's own arrival trace (cycle 0 for the
        # batch policies).  Arrival-aware policies expose arrivals_for.
        arrivals = sched.arrivals_for(batch) \
            if hasattr(sched, "arrivals_for") else (0,) * batch
        finish = completion_cycles(self._cts, assign, arrivals)
        hist = latency_histogram(f - a for f, a in zip(finish, arrivals))
        ws = _working_set(self.backend, self.instances, self.la, self.lb,
                          self.tile_b)
        return BankReport(batch=batch, cycles=cycles, instances=insts,
                          plan_throughput=self.plan.throughput,
                          working_set_bytes=ws, scheduler=sched.name,
                          latency_hist=hist)

    # -------------------------------------------------------------- execute
    def dispatch_fn(self, batch: int):
        """The pure (un-jitted) dispatch closure for one batch size.

        Exposed so :mod:`.sharded` can wrap it in shard_map; ``execute``
        wraps it in ``jax.jit``.
        """
        assign, _ = self.scheduler.schedule(self._cts, batch)
        return self._dispatch(assign, batch)

    def _dispatch(self, assign: tuple, batch: int):
        """The dispatch closure that runs ``batch`` ops as ``assign``."""
        if self.backend == "fused":
            from repro.kernels.bank_fold import make_fused_dispatch
            return make_fused_dispatch(assign, self.instances,
                                       self.la, self.lb, batch,
                                       signed=self._signed)
        idx = [np.asarray(ops, np.int32) for ops in assign]
        muls = self._muls
        la, lb = self.la, self.lb

        def run(a, b):
            out = jnp.zeros((batch, la + lb), L.LIMB_DTYPE)
            for ops, mul in zip(idx, muls):
                if ops.size == 0:
                    continue
                part = mul(a[ops], b[ops])
                out = out.at[ops].set(part)
            return out

        return run

    def _build(self, batch: int):
        """The jitted dispatch for ``batch``, the args of its launch span
        (the rows given and, on the fused backend, the rows the kernel
        computes) and the report of the same assignment.  Built once per
        batch size, so its ``bank.report`` span marks a cache miss."""
        with span("bank.report"):
            assign, cycles = self.scheduler.schedule(self._cts, batch)
            report = self._report(self.scheduler, batch, assign, cycles)
        run = self._dispatch(assign, batch)
        args = {"rows": batch}
        if hasattr(run, "kernel_rows"):
            args["kernel_rows"] = run.kernel_rows
        return jax.jit(run), args, report

    def launch_count(self, batch: int) -> int:
        """Pallas launches one bank round issues for this batch size.

        Traced from the dispatch jaxpr (no execution): exactly 1 on the
        fused path, 0 on the pure-jnp core path.
        """
        from repro.kernels.introspect import count_pallas_launches
        a = jnp.zeros((batch, self.la), L.LIMB_DTYPE)
        b = jnp.zeros((batch, self.lb), L.LIMB_DTYPE)
        return count_pallas_launches(self.dispatch_fn(batch), a, b)

    def execute(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """(B, LA) x (B, LB) -> (B, LA+LB) limbs, bit-exact."""
        if a.ndim == 1:
            return self.execute(a[None], b[None])[0]
        batch = a.shape[0]
        if b.shape[0] != batch:
            # without this, dispatch_fn's constant indices and slices
            # clamp out-of-range ops and silently return wrong products
            raise ValueError(
                f"batch mismatch: a has {batch} ops, b has {b.shape[0]}")
        if a.shape[-1] != self.la or b.shape[-1] != self.lb:
            raise ValueError(
                f"operand limbs {a.shape[-1]}x{b.shape[-1]} do not match "
                f"bank widths {self.la}x{self.lb}")
        entry = self._compiled.get(batch)
        if entry is None:
            if len(self._compiled) >= self.MAX_COMPILED:
                self._compiled.pop(next(iter(self._compiled)))
            entry = self._compiled[batch] = self._build(batch)
        fn, args, self.last_report = entry
        with span("bank.launch", **args):
            return fn(a, b)

    def describe(self) -> str:
        return (f"Bank[{self.plan.describe()}  backend={self.backend}  "
                f"scheduler={self.scheduler.name}  "
                f"{len(self.instances)} instances]")


# ------------------------------------------------------------------ module API

@functools.lru_cache(maxsize=64)
def _bank_for(plan: Plan, bits_a: int, bits_b: int, backend: str,
              scheduler: str = "round_robin") -> Bank:
    return Bank(plan, bits_a, bits_b, backend=backend, scheduler=scheduler)


def execute(plan: Plan, a: jax.Array, b: jax.Array, *,
            backend: str = "core",
            scheduler: str = "round_robin") -> jax.Array:
    """One-shot bank execution: dispatch a batch across ``plan``'s
    instances and return the (B, LA+LB) limb products.

    Operand bit widths are taken from the limb counts.  Banks are cached
    per (plan, widths, backend, scheduler), so repeated calls re-use the
    compiled dispatch.  Use ``last_report(plan, a, b)`` -- or a ``Bank``
    object directly -- for the cycle accounting.
    """
    la = a.shape[-1] if a.ndim > 1 else a.shape[0]
    lb = b.shape[-1] if b.ndim > 1 else b.shape[0]
    bank = _bank_for(plan, la * L.RADIX_BITS, lb * L.RADIX_BITS, backend,
                     scheduler)
    return bank.execute(a, b)


def last_report(plan: Plan, a: jax.Array, b: jax.Array, *,
                backend: str = "core",
                scheduler: str = "round_robin") -> BankReport:
    """Cycle-accounting report for the batch shape of (a, b)."""
    la = a.shape[-1] if a.ndim > 1 else a.shape[0]
    lb = b.shape[-1] if b.ndim > 1 else b.shape[0]
    bank = _bank_for(plan, la * L.RADIX_BITS, lb * L.RADIX_BITS, backend,
                     scheduler)
    batch = a.shape[0] if a.ndim > 1 else 1
    return bank.report(batch)

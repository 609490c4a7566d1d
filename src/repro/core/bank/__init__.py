"""Bank subsystem: executable multiplier banks for ``planner.Plan``s.

Three layers:

  :mod:`.schedule`  -- pluggable dispatch policies (``Scheduler``
                       protocol; round_robin / greedy / streaming), all
                       returning the same static (assignment, makespan)
                       contract so execution stays jit-compatible.
  :mod:`.engine`    -- the ``Bank`` class wiring a Plan and a scheduler
                       into bit-exact, cycle-accounted execution on one
                       of two backends: "core" (pure-jnp ``mcim_mul``
                       per instance, the CPU path and the reference) or
                       "fused" (the whole round as one
                       ``kernels.bank_fold`` Pallas launch).
  :mod:`.sharded`   -- N replicated banks over a mesh axis
                       (``sharded_execute``) via ``jax.shard_map``.

New code should usually not construct ``Bank`` objects directly:
:mod:`repro.designs` compiles a declarative ``DesignSpec`` into a
``CompiledDesign`` that owns the bank plus timing/area/provenance.
"""
from .schedule import (Scheduler, RoundRobinScheduler, GreedyScheduler,
                       StreamingScheduler, SCHEDULERS, register_scheduler,
                       get_scheduler, round_robin_schedule, greedy_schedule,
                       streaming_schedule, uniform_arrivals,
                       completion_cycles, latency_histogram,
                       histogram_percentile)
from .engine import (BACKENDS, Bank, BankReport, InstanceReport, execute,
                     last_report)
from .sharded import sharded_execute, sharded_report

__all__ = [
    # schedule layer
    "Scheduler", "RoundRobinScheduler", "GreedyScheduler",
    "StreamingScheduler", "SCHEDULERS", "register_scheduler",
    "get_scheduler", "round_robin_schedule", "greedy_schedule",
    "streaming_schedule", "uniform_arrivals",
    "completion_cycles", "latency_histogram", "histogram_percentile",
    # engine
    "BACKENDS", "Bank", "BankReport", "InstanceReport", "execute", "last_report",
    # distribution layer
    "sharded_execute", "sharded_report",
]

"""Bank subsystem: executable multiplier banks for ``planner.Plan``s.

The PR-2 ``core/bank.py`` monolith is now three decoupled layers:

  :mod:`.schedule`  -- pluggable dispatch policies (``Scheduler``
                       protocol; round_robin / greedy / streaming), all
                       returning the same static (assignment, makespan)
                       contract so execution stays jit-compatible.
  :mod:`.backends`  -- ``InstanceBackend`` registry keyed by
                       (arch, capability): how one instance multiplies
                       (pure-jnp core or Pallas kernels, incl. the
                       folded Karatsuba CT=3 kernel schedule).
  :mod:`.engine`    -- the ``Bank`` class wiring a Plan, a scheduler and
                       backends into bit-exact, cycle-accounted
                       execution.
  :mod:`.sharded`   -- N replicated banks over a mesh axis
                       (``sharded_execute``) via ``jax.shard_map``
                       + launch-layer partition specs.

This package is a drop-in replacement for the old module:
``from repro.core import bank`` and every public PR-2 name
(``Bank``, ``BankReport``, ``execute``, ``last_report``,
``round_robin_schedule``, ``BACKENDS``) keep working.  New code should
usually not construct ``Bank`` objects directly: :mod:`repro.designs`
compiles a declarative ``DesignSpec`` into a ``CompiledDesign`` that
owns the bank plus timing/area/provenance.
"""
from .schedule import (Scheduler, RoundRobinScheduler, GreedyScheduler,
                       StreamingScheduler, SCHEDULERS, register_scheduler,
                       get_scheduler, round_robin_schedule, greedy_schedule,
                       streaming_schedule, uniform_arrivals,
                       completion_cycles, latency_histogram,
                       histogram_percentile)
from .backends import (InstanceBackend, BACKENDS, CAPABILITIES,
                       register_backend, get_backend, registered_backends)
from .engine import (Bank, BankReport, InstanceReport, execute, last_report)
from .sharded import sharded_execute, sharded_report

__all__ = [
    # schedule layer
    "Scheduler", "RoundRobinScheduler", "GreedyScheduler",
    "StreamingScheduler", "SCHEDULERS", "register_scheduler",
    "get_scheduler", "round_robin_schedule", "greedy_schedule",
    "streaming_schedule", "uniform_arrivals",
    "completion_cycles", "latency_histogram", "histogram_percentile",
    # backend layer
    "InstanceBackend", "BACKENDS", "CAPABILITIES", "register_backend",
    "get_backend", "registered_backends",
    # engine
    "Bank", "BankReport", "InstanceReport", "execute", "last_report",
    # distribution layer
    "sharded_execute", "sharded_report",
]

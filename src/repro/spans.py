"""The program's host spans in the JAX profiler's trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``PREFIX + name``: it lands on the profiler's host plane, on the same
clock as the device's operations, with ``args`` as the event's stats.
Nothing is recorded unless a profiler session is running; an idle
annotation costs about a microsecond.

Spans: ``mcim.mul`` (all of ``CompiledDesign.mul``), ``mcim.bank.report``
(``Bank.report``, and the report ``Bank.execute`` builds beside a new
compiled dispatch) and ``mcim.bank.launch`` (the call of the compiled
dispatch, with ``rows`` given and, on the fused backend, the
``kernel_rows`` the kernel computes).

``Bank.execute`` builds its report once per batch size, with the
dispatch, so an ``mcim.bank.report`` inside an ``mcim.mul`` marks a
dispatch-cache miss: their count divided by the count of ``mcim.mul``
is the miss share, 0 in a window whose batch sizes are all warm.

On a mesh (``core/bank/sharded.py``) the replicated banks build no
report.  ``mcim.bank.sharded_build`` marks a miss of the sharded
dispatch cache (the first call at a new plan, mesh and shard size): it
holds the build of the replicas' dispatch, whose compile then shows in
the launch.  That launch also carries ``shards`` (the mesh axis size)
and ``local_rows`` (rows per shard); its ``rows`` and ``kernel_rows``
are summed over the shards.
"""
from __future__ import annotations

import jax

PREFIX = "mcim."


def span(name: str, **args):
    """Context manager: the host span ``PREFIX + name`` with ``args``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)

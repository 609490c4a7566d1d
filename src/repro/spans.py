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
"""
from __future__ import annotations

import jax

PREFIX = "mcim."


def span(name: str, **args):
    """Context manager: the host span ``PREFIX + name`` with ``args``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)

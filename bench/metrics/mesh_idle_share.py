"""Share of the traced window in which at least one of the cell's chips
runs no operation of the program: the union of every chip's idle gaps,
or 1 less the intersection of their busy intervals.  Its excess over
``device_idle_share`` (the mean over chips) is the chips' stagger.
Nothing to read on one chip."""
from bench import trace as tr


def read(run):
    trace = run.trace
    if trace is None or len(trace.device_names()) < 2:
        return None
    lo, hi = trace.window
    gaps = [tr.Event("idle", s, e)
            for d in trace.device_names()
            for s, e in tr.idle_gaps(trace.program_ops(d), lo, hi)]
    return tr.union_ns(gaps, lo, hi) / (hi - lo) * 100.0

"""Spread of the program's busy time over the cell's chips: (largest
less smallest ``busy_s``) over their mean, in percent.  A replica that
straggles, or one that waits on the others, widens it.  Nothing to read
on one chip."""


def read(run):
    trace = run.trace
    if trace is None or len(trace.device_names()) < 2:
        return None
    busy = [trace.busy_s(d) for d in trace.device_names()]
    mean = sum(busy) / len(busy)
    if not mean:
        return None
    return (max(busy) - min(busy)) / mean * 100.0

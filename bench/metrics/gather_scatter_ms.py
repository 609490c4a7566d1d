"""Device time per call of the program's operations other than the
``bank_fold`` kernel (the dispatch's gather, scatter and glue),
averaged over the cell's chips."""


def read(run):
    trace = run.trace
    if trace is None or not run.calls:
        return None
    devices = trace.device_names()
    if not devices:
        return None
    kernel = run.metric("bank_fold_ms")
    if kernel is None:
        return None
    total = sum(e.seconds for d in devices for e in trace.program_ops(d))
    return total / len(devices) / len(run.calls) * 1e3 - kernel

"""Share of the traced window in which no operation of the program ran
on the device, averaged over the cell's chips."""


def read(run):
    trace = run.trace
    if trace is None or not trace.device_names():
        return None
    return (1.0 - trace.mean_busy_s() / trace.window_s) * 100.0

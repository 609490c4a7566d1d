"""Products completed and checked in the window, over its wall time
(padding rows are no products)."""


def read(run):
    if not run.calls:
        return None
    return run.batch * len(run.calls) / run.window_s

"""Mean host time per call from the call until ``mul`` returns, before
the wait: the bank engine's host path (``Bank.execute`` and its
per-call report, or the sharded dispatch), and the copy of the operands
to the device where the traffic keeps them on the host."""


def read(run):
    if not run.calls:
        return None
    return sum(ret - issue for issue, ret, _ in run.calls) \
        / len(run.calls) * 1e3

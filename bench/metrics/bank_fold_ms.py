"""Device time per call of the fused ``bank_fold`` kernel, averaged
over the cell's chips.

The kernel's events carry the name of the jitted function that makes
the launch, ``fused_bank_mul`` (``kernels/bank_fold/kernel.py``).
"""
KERNEL = "fused_bank_mul"


def kernel_events(trace, device):
    return [e for e in trace.program_ops(device)
            if e.name.split(".")[0] == KERNEL]


def read(run):
    trace = run.trace
    if trace is None or not run.calls:
        return None
    devices = trace.device_names()
    per_device = [sum(e.seconds for e in kernel_events(trace, d))
                  for d in devices]
    if not any(per_device):
        return None
    return sum(per_device) / len(devices) / len(run.calls) * 1e3

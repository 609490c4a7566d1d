"""Share of its roofline that the ``bank_fold`` kernel reaches: the
least time the call's interface bytes take at the chip's peak HBM rate,
over the kernel's device time per call.  The rows are those ``mul`` is
given, padding included.  The 32-bit integer rate of the VPU is not
published, so the bound is the bytes'."""
from bench import work


def read(run):
    kernel_ms = run.metric("bank_fold_ms")
    if kernel_ms is None or run.peaks is None:
        return None
    per_chip = run.rows // run.chips
    least_s = work.interface_bytes(per_chip, run.la, run.lb) \
        / run.peaks["hbm_bytes_per_s"]
    return least_s / (kernel_ms * 1e-3) * 100.0

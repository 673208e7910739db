"""Device seconds per scan of the Pallas back-projection kernel
(`backproject_dual`, kernels/backproject/kernel.py), the mean over the
chips."""
from bench import devtrace


def read(run):
    if run.trace is None:
        return None
    return devtrace.per_scan(run.trace, devtrace.is_bp_kernel)

"""The device's idle share of the window: 1 - busy / window, where busy is
the union of the intervals in which an operation ran, the mean over the
chips."""
from bench import devtrace


def read(run):
    if run.trace is None:
        return None
    busy = devtrace.busy_seconds(run.trace)
    lo, hi = devtrace.window(run.trace)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / ((hi - lo) / 1e9))

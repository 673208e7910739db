"""Device seconds per scan of the FFT operations, the mean over the chips:
only the ramp filter (core/filtering.py) takes FFTs."""
from bench import devtrace


def read(run):
    if run.trace is None:
        return None
    return devtrace.per_scan(run.trace, devtrace.is_fft)

"""The back-projection kernel's share of its roofline: the least time a
scan's back-projection could take on the chips (bench/roofline.py, counted
from the geometry), over the kernel's device seconds per scan."""
from bench import devtrace, roofline


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    kernel_s = devtrace.per_scan(run.trace, devtrace.is_bp_kernel)
    if kernel_s is None:
        return None
    least, bound = roofline.least_time(run.geometry, run.storage_bytes,
                                       run.peaks, run.n_chips)
    print(f"bp_roofline_pct: least time {least!r} s per scan, "
          f"{bound}-bound; kernel {kernel_s!r} s per scan", flush=True)
    return 100.0 * least / kernel_s

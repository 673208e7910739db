"""The whole scan's share of the chips' peak: the operations the scan's
back-projection needs (bench/roofline.py, counted from the geometry) per
second of time to volume in the traced window, over the peak of all the
chips. It bounds what any kernel's roofline share can gain end to end."""
from bench import roofline


def read(run):
    if run.peaks is None:
        return None
    per_scan = run.window_s / run.n_scans
    return 100.0 * roofline.operations(run.geometry) / (
        per_scan * run.n_chips * run.peaks["flops_per_s"])

"""Time to volume per scan, storage to storage: the window's wall time,
from the first scan's start to the last scan's end, over its scans."""


def read(run):
    return run.window_s / run.n_scans

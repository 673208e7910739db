"""Set-up: process start, making and storing the projections, planning,
building (compiling, in a run that compiles) and one warm-up scan."""


def read(run):
    return run.setup_s

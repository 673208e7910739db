"""Device seconds per scan of the row reduce, the mean over the chips: the
operations whose JAX scope holds the engine's `fdk.reduce` stage
(core/plan.py STAGE_SCOPES). On one chip the stage holds no operation."""
from bench import devtrace

SCOPE = "fdk.reduce"


def in_scope(name, opcode, scope):
    return SCOPE in scope.split("/")


def read(run):
    if run.trace is None:
        return None
    return devtrace.per_scan(run.trace, in_scope)

"""Device seconds per scan of the column AllGather, the mean over the
chips: the operations whose JAX scope holds the engine's `fdk.gather` stage
(core/plan.py STAGE_SCOPES). On one chip the stage holds no operation."""
from bench import devtrace

SCOPE = "fdk.gather"


def in_scope(name, opcode, scope):
    return SCOPE in scope.split("/")


def read(run):
    if run.trace is None:
        return None
    return devtrace.per_scan(run.trace, in_scope)

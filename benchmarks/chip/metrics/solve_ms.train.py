"""Device time under the ``admm.solve`` scope (the right-hand side and
the triangular solves against the cached factor, K times) per layer
program, as a mean over layer programs and chips (trace: each op's
``tf_op`` scope path)."""
from benchmarks.chip import program_trace


def read(r):
    names = program_trace.names()
    return None if names is None else program_trace.scope_ms_per_program(r, names.SOLVE)

"""Device time under the ``backbone.ssd`` scope (the state-space scan of every Mamba2 layer) per
execution of a serving bucket program, as a mean over the executions of
the traced window (trace: each op's ``tf_op`` scope path)."""
from statistics import mean

from benchmarks.chip import program_trace, serve_trace


def read(r):
    names = program_trace.names()
    if names is None or not hasattr(names, "SSD"):
        return None
    per = serve_trace.scope_ms(r, names.SSD)
    return None if per is None else mean(per)

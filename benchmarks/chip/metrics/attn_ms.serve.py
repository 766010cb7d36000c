"""Device time under the ``backbone.attention`` scope (every attention mixer: projections, scores, softmax, output) per
execution of a serving bucket program, as a mean over the executions of
the traced window (trace: each op's ``tf_op`` scope path)."""
from statistics import mean

from benchmarks.chip import program_trace, serve_trace


def read(r):
    names = program_trace.names()
    if names is None or not hasattr(names, "ATTENTION"):
        return None
    per = serve_trace.scope_ms(r, names.ATTENTION)
    return None if per is None else mean(per)

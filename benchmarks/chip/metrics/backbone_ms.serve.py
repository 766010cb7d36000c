"""Device time under the ``features.backbone`` scope (the whole extractor: embedding, every layer, pooling) per
execution of a serving bucket program, as a mean over the executions of
the traced window (trace: each op's ``tf_op`` scope path)."""
from statistics import mean

from benchmarks.chip import program_trace, serve_trace


def read(r):
    names = program_trace.names()
    if names is None or not hasattr(names, "BACKBONE"):
        return None
    per = serve_trace.scope_ms(r, names.BACKBONE)
    return None if per is None else mean(per)

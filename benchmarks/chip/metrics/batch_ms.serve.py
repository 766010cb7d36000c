"""Mean time to serve one coalesced batch on the host's clock: pack,
forward (pad, bucket program, block) and scatter (the ``serve.batch``
spans; trace: host spans)."""
from benchmarks.chip import program_trace


def read(r):
    names = program_trace.names()
    return None if names is None else program_trace.mean_span_ms(r, names.BATCH)

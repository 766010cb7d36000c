"""Mean time a submitting thread waits for the runtime's lock (the
``serve.lock_wait`` spans; trace: host spans)."""
from benchmarks.chip import program_trace


def read(r):
    names = program_trace.names()
    return None if names is None else program_trace.mean_span_ms(r, names.LOCK_WAIT)

"""Median over requests of the wait between admission and service: the
start of the ``serve.batch`` span whose ``first``..``last`` id range
holds the request, less the end of the request's ``serve.submit`` span
(trace: host spans and their arguments)."""
from benchmarks.chip import program_trace


def read(r):
    names = program_trace.names()
    return None if names is None else program_trace.queue_wait_ms(r, names.SUBMIT, names.BATCH)

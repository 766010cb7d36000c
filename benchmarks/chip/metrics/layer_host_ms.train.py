"""The host's own time per layer: each ``dssfn.layer`` span less the
``dssfn.host_sync`` spans inside it (dispatch, weight building and the
loop's bookkeeping), as a mean over the layers of the window (trace:
host spans)."""
from benchmarks.chip import program_trace


def read(r):
    names = program_trace.names()
    return None if names is None else program_trace.self_ms(r, names.LAYER, names.HOST_SYNC)

"""Device-busy time of one execution of a bucket program (the engine's
jitted ``forward_program``), as a mean over the executions (trace)."""
from statistics import mean

from benchmarks.chip import tracing

#: The module name XLA gives the engine's bucket programs.
MODULE = "jit_forward_program"


def read(r):
    times = tracing.module_busy_s(r.trace.chips[0], MODULE)
    return 1e3 * mean(times) if times else None

"""Share of the traced window in which no operation ran on the device,
as a mean over the chips (trace: union of the op intervals)."""
from benchmarks.chip import tracing


def read(r):
    lo, hi = r.window
    busy = sum(tracing.busy_s(c, lo, hi) for c in r.trace.chips) / len(r.trace.chips)
    return 100.0 * (1.0 - busy / ((hi - lo) * 1e-9))

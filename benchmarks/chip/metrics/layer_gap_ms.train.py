"""Device-idle time between consecutive layer programs of one train, per
gap, as a mean over the chips (trace)."""
from statistics import mean

from benchmarks.chip import tracing


def read(r):
    per_chip = [tracing.interlayer_idle_s(chip, progs)
                for chip, progs in zip(r.trace.chips, r.counters["programs"])]
    if not all(per_chip):
        return None
    return 1e3 * mean(mean(gaps) for gaps in per_chip)

"""Device time in collective instructions per layer program, as a mean
over layer programs and chips (trace)."""
from statistics import mean


def read(r):
    times = [p.collective_s * 1e3 for progs in r.counters["programs"] for p in progs]
    if not times or not any(times):
        return None
    return mean(times)

"""Device time of the ADMM scan (the K-iteration ``while``) per layer
program, as a mean over layer programs and chips (trace)."""
from statistics import mean


def read(r):
    scans = [(p.scan.end - p.scan.start) * 1e-6
             for progs in r.counters["programs"] for p in progs]
    return mean(scans) if scans else None

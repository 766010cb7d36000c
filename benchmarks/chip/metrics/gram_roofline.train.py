"""Least time the chip could take for the propagate + Gram stage (work
counted from the shapes, ``work.gram_stage``, at the published peaks)
over the device time of that stage's instructions, per chip (trace)."""
from benchmarks.chip import peaks, work


def read(r):
    sizes, chips = r.counters["sizes"], r.counters["chips"]
    least = spent = 0.0
    for progs in r.counters["programs"]:
        by_train = {}
        for p in progs:
            if p.train is not None:
                by_train.setdefault(p.train, []).append(p)
        for train in by_train.values():
            if len(train) != sizes.layers + 1:
                continue
            for layer, p in enumerate(train):
                w = work.gram_stage(sizes, layer)
                least += peaks.least_time_s(w.flops / chips, w.nbytes / chips, r.peak)[0]
                spent += p.gram_s
    if spent <= 0.0:
        return None
    return 100.0 * least / spent

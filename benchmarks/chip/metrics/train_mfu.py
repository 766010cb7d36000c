"""Operations a train needs (``work.train_flops``) times the trains of the
traced window, over the window and the chips' bf16 peak."""
from benchmarks.chip import work


def read(r):
    lo, hi = r.window
    flops = work.train_flops(r.counters["sizes"]) * r.counters["trains"]
    return 100.0 * flops / ((hi - lo) * 1e-9) / (r.counters["chips"] * r.peak.flops_per_s)

"""Least time the chip could take for the state-space scans of the
traced window (``work_granite.ssd_work`` over the bucket tokens the
bucket programs ran, at the published peaks) over the device time under
the ``backbone.ssd`` scope in those programs (trace)."""
from benchmarks.chip import peaks, program_trace, serve_trace, work_granite


def read(r):
    names = program_trace.names()
    if names is None or not hasattr(names, "SSD") or not r.counters.get("bucket_tokens"):
        return None
    per = serve_trace.scope_ms(r, names.SSD)
    if per is None:
        return None
    w = work_granite.ssd_work(r.counters["sizes"], r.counters["bucket_tokens"])
    least = peaks.least_time_s(w.flops, w.nbytes, r.peak)[0]
    return 100.0 * least / (sum(per) * 1e-3)

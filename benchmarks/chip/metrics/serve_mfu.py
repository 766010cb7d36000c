"""Operations the texts answered in the traced window need
(``work_granite.text_flops`` of each text's real length, backbone and
stack), over the window and the chip's bf16 peak."""


def read(r):
    flops = r.counters.get("real_flops")
    if not flops:
        return None
    lo, hi = r.window
    return 100.0 * flops / ((hi - lo) * 1e-9) / r.peak.flops_per_s

"""The trace reduction, on a small trace recorded on a TPU v5e chip
(``benchmarks/chip/testdata/README.txt`` says what it holds)."""
from pathlib import Path

import pytest

from benchmarks.chip import tracing

TRACE = Path(__file__).resolve().parents[2] / "benchmarks/chip/testdata/small_train.xplane.pb"
#: The recorded train's ADMM iterations and per-worker samples.
K, J_M = 4, 256


@pytest.fixture(scope="module")
def trace():
    return tracing.load(str(TRACE))


def test_planes_and_host_spans(trace):
    assert len(trace.chips) == 1
    assert len(trace.spans("bench.train")) == 1
    chip = trace.chips[0]
    assert chip.ops and chip.modules
    with pytest.raises(ValueError, match="bench.window"):
        trace.window


def test_layer_programs_found_by_their_scan(trace):
    trains = trace.spans("bench.train")
    progs = tracing.layer_programs(trace.chips[0], trains, scan_repeats=K,
                                   sample_dim=J_M)
    assert len(progs) == 3                      # layers 0, 1 and 2
    for p in progs:
        assert p.module.start <= p.scan.start < p.scan.end <= p.module.end
        assert p.gram_s > 0 and p.collective_s == 0 and p.train == 0
    # A repeat count no loop has finds no layer program.
    assert tracing.layer_programs(trace.chips[0], trains, scan_repeats=3 * K,
                                  sample_dim=J_M) == []
    gaps = tracing.interlayer_idle_s(trace.chips[0], progs)
    assert len(gaps) == 2 and all(g >= 0 for g in gaps)


def test_busy_and_breakdown_within_the_span(trace):
    span = trace.spans("bench.train")[0]
    window_s = (span.end - span.start) * 1e-9
    busy = tracing.busy_s(trace.chips[0], span.start, span.end)
    assert 0 < busy < window_s
    progs = tracing.layer_programs(trace.chips[0], [span], scan_repeats=K, sample_dim=J_M)
    ops = tracing.top_device_ops(trace, span.start, span.end,
                                 scans=[[(p.scan.start, p.scan.end) for p in progs]])
    assert len(ops) <= 10 and ops[0][0].startswith("scan/")
    assert sum(s for _, s in ops) <= busy
    gaps = tracing.top_idle_gaps(trace, span.start, span.end)
    assert gaps and all(name and s > 0 for name, s in gaps)
    assert sum(s for _, s in gaps) <= window_s - busy + 1e-9


def test_union_and_idle_gaps():
    iv = [(0, 4), (2, 6), (8, 9), (20, 30)]
    assert tracing.union_length(iv, 0, 10) == 7
    assert tracing.union_length(iv, 5, 25) == 1 + 1 + 5
    assert tracing.idle_gaps(iv, 0, 12) == [(6, 8), (9, 12)]
    assert tracing.idle_gaps([], 1, 2) == [(1, 2)]


def test_leaves_drop_loop_containers():
    E = tracing.Event
    ops = [E("%while.1 = ...", 0, 10), E("%a.1 = ...", 1, 2), E("%b.2 = ...", 3, 4),
           E("%c.3 = ...", 11, 12)]
    assert [o.name for o in tracing.leaves(ops)] == ["%a.1 = ...", "%b.2 = ...", "%c.3 = ..."]


@pytest.mark.parametrize("name, cls, coll", [
    ('%custom-call.57 = f32[4,1,128,128]{...} custom-call(...), custom_call_target="Cholesky"',
     "custom-call:Cholesky", False),
    ("%fusion.642 = f32[20,1020,10]{...} fusion(...)", "fusion", False),
    ("%collective-permute-start.3 = (f32[10,1020]) collective-permute-start(...)",
     "collective-permute-start", True),
    ("%all-reduce.12 = f32[] all-reduce(...)", "all-reduce", True),
])
def test_op_classes(name, cls, coll):
    assert tracing.op_class(name) == cls
    assert tracing.is_collective(name) == coll


def test_sample_dimension_match_is_exact():
    assert tracing.touches_dim("%f = f32[4,784,256]{2,1,0} fusion(...)", 256)
    assert not tracing.touches_dim("%f = f32[4,784,2560]{2,1,0} fusion(...)", 256)

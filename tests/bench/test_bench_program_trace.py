"""The readers of the program's own scopes and spans
(``benchmarks/chip/program_trace.py`` and the metrics that use it), on
small traces recorded on a TPU v5e chip: ``small_train.xplane.pb``, from
a program without scopes (``README.txt``), and the two scoped traces
``SCOPED.txt`` describes."""
import math
import shutil
from pathlib import Path

import pytest

from benchmarks.chip import harness, program_trace, tracing

TESTDATA = Path(__file__).resolve().parents[2] / "benchmarks/chip/testdata"
UNSCOPED = TESTDATA / "small_train.xplane.pb"
SCOPED_TRAIN = TESTDATA / "scoped_train.xplane.pb"
SCOPED_SERVE = TESTDATA / "scoped_serve.xplane.pb"
#: The recorded trains' ADMM iterations, per-worker samples and layers + 1.
K, J_M, PROGRAMS = 4, 256, 3
#: Requests the recorded serving window answered, of 200 (one expired).
ANSWERED = 199
TRAIN_READERS = ("solve_ms.train", "mix_ms.train", "sync_idle_ms.train",
                 "layer_host_ms.train")
SERVE_READERS = ("queue_wait_ms.serve", "lock_wait_ms.serve", "batch_ms.serve")


def _as_current_run(monkeypatch, tmp_path, xplane: Path):
    """Place ``xplane`` where ``run.py`` leaves a traced run's profile."""
    dest = tmp_path / "cell.1" / "plugins" / "profile" / "t"
    dest.mkdir(parents=True)
    shutil.copy(xplane, dest / xplane.name)
    monkeypatch.setattr(program_trace, "TRACE_ROOT", tmp_path)


def _train_readings(path: Path, window_span: str) -> harness.Readings:
    trace = tracing.load(str(path))
    (span,) = trace.spans(window_span)
    progs = [tracing.layer_programs(chip, trace.spans("bench.train"),
                                    scan_repeats=K, sample_dim=J_M)
             for chip in trace.chips]
    return harness.Readings(trace=trace, window=(span.start, span.end), peak=None,
                            counters={"programs": progs, "trains": 1, "chips": 1})


def test_decoder_maps_the_solve_custom_call_to_its_scope_path():
    (chip,) = program_trace.device_tf_ops(UNSCOPED.read_bytes())
    (name,) = [n for n in chip if n.startswith("%custom-call.144 = ")]
    assert "InvertDiagBlocksLowerTriangular" in name
    (path,) = chip[name]
    assert "triangular_solve" in path
    assert program_trace.in_scope(path, "while")
    assert not program_trace.in_scope(path, "admm.solve")


def test_unscoped_program_reads_nothing(monkeypatch, tmp_path):
    _as_current_run(monkeypatch, tmp_path, UNSCOPED)
    r = _train_readings(UNSCOPED, "bench.train")
    assert len(r.counters["programs"][0]) == PROGRAMS
    for metric in TRAIN_READERS:
        assert harness.load_reader(metric)(r) is None, metric


def test_no_names_in_the_program_reads_nothing(monkeypatch, tmp_path):
    _as_current_run(monkeypatch, tmp_path, SCOPED_TRAIN)
    monkeypatch.setattr(program_trace, "names", lambda: None)
    r = _train_readings(SCOPED_TRAIN, tracing.WINDOW_SPAN)
    for metric in TRAIN_READERS:
        assert harness.load_reader(metric)(r) is None, metric


def test_no_trace_reads_nothing(monkeypatch, tmp_path):
    monkeypatch.setattr(program_trace, "TRACE_ROOT", tmp_path)
    r = _train_readings(SCOPED_TRAIN, tracing.WINDOW_SPAN)
    for metric in ("solve_ms.train", "mix_ms.train"):
        assert harness.load_reader(metric)(r) is None, metric


@pytest.fixture
def scoped_train(monkeypatch, tmp_path):
    _as_current_run(monkeypatch, tmp_path, SCOPED_TRAIN)
    return _train_readings(SCOPED_TRAIN, tracing.WINDOW_SPAN)


@pytest.mark.parametrize("metric", TRAIN_READERS)
def test_train_readers_read_the_scoped_trace(scoped_train, metric):
    value = harness.load_reader(metric)(scoped_train)
    assert value is not None and math.isfinite(value) and value > 0


def test_scope_sums_lie_within_the_layer_program(scoped_train):
    names = program_trace.names()
    chip, progs = scoped_train.trace.chips[0], scoped_train.counters["programs"][0]
    assert len(progs) == PROGRAMS
    solve = program_trace.scope_ms_per_program(scoped_train, names.SOLVE)
    mix = program_trace.scope_ms_per_program(scoped_train, names.MIX)
    admm = program_trace.scope_ms_per_program(scoped_train, names.ADMM)
    # Not bounded by the scan's own time: XLA hoists the solve's
    # loop-invariant work out of the loop, and the scope follows it.
    assert 0 < solve + mix <= admm
    busy_ms = sum(tracing.union_length(
        ((o.start, o.end) for o in chip.leaves_in(p.module.start, p.module.end)),
        p.module.start, p.module.end) for p in progs) * 1e-6
    scopes = [names.PROPAGATE, names.GRAM, names.CHOLESKY, names.ADMM]
    total = sum(program_trace.scope_ms_per_program(scoped_train, s) or 0.0
                for s in scopes) * PROGRAMS
    assert 0 < total <= busy_ms
    for scope in (names.GRAM, names.CHOLESKY, names.UPDATE):
        assert program_trace.scope_ms_per_program(scoped_train, scope) > 0, scope


def test_sync_idle_lies_within_the_layer_spans(scoped_train):
    names = program_trace.names()
    layers = program_trace.host_spans(scoped_train, names.LAYER)
    assert [s.name for s in layers] == [names.LAYER] * PROGRAMS
    idle = program_trace.sync_idle_ms_per_train(scoped_train, names.HOST_SYNC)
    host = program_trace.self_ms(scoped_train, names.LAYER, names.HOST_SYNC)
    total_ms = sum(s.end - s.start for s in layers) * 1e-6
    assert 0 < idle < total_ms and 0 < host * PROGRAMS < total_ms


@pytest.fixture
def scoped_serve(monkeypatch, tmp_path):
    _as_current_run(monkeypatch, tmp_path, SCOPED_SERVE)
    trace = tracing.load(str(SCOPED_SERVE))
    return harness.Readings(trace=trace, window=trace.window, peak=None, counters={})


@pytest.mark.parametrize("metric", SERVE_READERS)
def test_serve_readers_read_the_scoped_trace(scoped_serve, metric):
    value = harness.load_reader(metric)(scoped_serve)
    assert value is not None and math.isfinite(value) and value >= 0


def test_serve_batches_hold_each_answered_request_once(scoped_serve):
    names = program_trace.names()
    pt = program_trace.current()
    lo, hi = scoped_serve.window
    submitted = [s.args["request"] for s in pt.spans(names.SUBMIT, lo, hi)]
    served = [i for b in pt.spans(names.BATCH, lo, hi)
              for i in range(b.args["first"], b.args["last"] + 1)]
    assert sorted(submitted) == list(range(len(submitted)))
    assert len(set(served)) == len(served) == ANSWERED
    assert set(served) <= set(submitted)
    batch_ms = harness.load_reader("batch_ms.serve")(scoped_serve)
    forward = [s.end - s.start for s in pt.spans(names.FORWARD, lo, hi)]
    assert 0 < sum(forward) * 1e-6 / len(forward) <= batch_ms


def test_overlap_of_interval_lists():
    a = [(0, 4), (6, 10), (20, 30)]
    b = [(2, 7), (9, 25)]
    assert program_trace.overlap_ns(a, b) == 2 + 1 + 1 + 5
    assert program_trace.overlap_ns(a, []) == 0


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(counted)/vmap()/dssfn.admm/while/body/admm.solve/triangular_solve:", "admm.solve", True),
    ("jit(counted)/vmap()/dssfn.admm/while/body/admm.mix/dot_general:", "admm.mix", True),
    ("jit(counted)/vmap()/dssfn.admm/while/body/admm.solve_x/add:", "admm.solve", False),
    ("jit(counted)/dssfn.gram:", "dssfn.gram", True),
    ("jit(counted)/vmap(dssfn.admm)/while/body/closed_call/admm.mix/add:", "dssfn.admm", True),
    ("jit(counted)/vmap(dssfn.admm)/while:", "admm", False),
])
def test_scope_is_a_whole_path_component(path, scope, inside):
    assert program_trace.in_scope(path, scope) == inside

"""The program's profiler scopes and spans (``repro.profiling``): the layer
loop's host spans under a real profiler, the serving runtime's batch spans
against the requests they served, and the scope names in a layer
program's op metadata."""
import glob
import os
import warnings

import jax
import numpy as np
import pytest

from repro import dssfn, profiling
from repro.analysis import synthetic_serve_engine
from repro.core import engine, ssfn
from repro.core.backend import SimulatedBackend
from repro.serve import ManualClock, ServeRuntime


def _host_spans(trace_dir) -> dict:
    """Span name -> ``(start_ns, args)`` of each host span, by start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    with warnings.catch_warnings():
        # jaxlib's stats type warns that it names no module when read.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                if plane.name == "/host:CPU" and line.name.startswith("python"):
                    for e in line.events:
                        out.setdefault(e.name, []).append((e.start_ns, dict(e.stats)))
    return {k: [args for _, args in sorted(v, key=lambda s: s[0])]
            for k, v in out.items()}


def _traced(fn, trace_dir):
    jax.profiler.start_trace(str(trace_dir))
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def test_train_emits_a_layer_span_per_layer(tmp_path):
    m, p, q, jm, layers = 4, 8, 3, 16, 2
    kx, kt = jax.random.split(jax.random.PRNGKey(0))
    xw = jax.random.normal(kx, (m, p, jm))
    tw = jax.nn.one_hot(jax.random.randint(kt, (m, jm), 0, q), q).transpose(0, 2, 1)
    spec = dssfn.TrainSpec(
        cfg=ssfn.SSFNConfig(input_dim=p, num_classes=q, num_layers=layers,
                            hidden=20, admm_iters=4),
        backend="simulated", workers=m, policy="gossip:2:1", trace_every=0,
    )
    dssfn.train(spec, xw, tw, jax.random.PRNGKey(1))      # compile outside the trace
    res = _traced(lambda: dssfn.train(spec, xw, tw, jax.random.PRNGKey(1)), tmp_path)
    jax.block_until_ready(res.params.o)
    spans = _host_spans(tmp_path)
    assert [a["layer"] for a in spans[profiling.LAYER]] == list(range(layers + 1))
    # The jitter fetch is the only per-layer sync of the default train.
    assert len(spans[profiling.HOST_SYNC]) == layers + 1
    assert len(spans[profiling.DISPATCH]) == layers + 1
    assert len(spans[profiling.BUILD_WEIGHT]) == layers


def test_serve_batches_cover_each_completed_request_once(tmp_path):
    engine_ = synthetic_serve_engine(buckets=(1, 4, 8))
    rt = ServeRuntime(engine_, clock=ManualClock(), max_batch=8,
                      max_pending_samples=64).start()
    rng = np.random.default_rng(0)
    requests = [rng.standard_normal((6, int(rng.integers(1, 6)))).astype(np.float32)
                for _ in range(40)]
    requests[7][0, 0] = np.nan                          # rejected at admission
    rt.submit(requests[0])
    rt.flush()                                          # warm the buckets

    def serve():
        handles = []
        for i, x in enumerate(requests):
            handles.append(rt.submit(x))
            if i % 5 == 4:
                rt.tick()
        rt.drain()
        return handles

    handles = _traced(serve, tmp_path)
    spans = _host_spans(tmp_path)
    ids = [h.request_id for h in handles]
    assert ids == list(range(1, len(requests) + 1))
    assert sorted(a["request"] for a in spans[profiling.SUBMIT]) == ids
    assert sorted(a["request"] for a in spans[profiling.LOCK_WAIT]) == ids
    covered = [i for a in spans[profiling.BATCH] for i in range(a["first"], a["last"] + 1)]
    completed = [h.request_id for h in handles if h.ok()]
    assert len(completed) == len(requests) - 1
    assert sorted(i for i in covered if i in completed) == completed
    for a in spans[profiling.BATCH]:
        assert 1 <= a["requests"] <= a["last"] - a["first"] + 1
        assert a["bucket"] == engine_.bucket_for(a["samples"])
    for name in (profiling.PACK, profiling.FORWARD, profiling.SCATTER):
        assert len(spans[name]) == len(spans[profiling.BATCH])
    assert profiling.FLUSH in spans


@pytest.mark.parametrize("with_weight", [False, True])
def test_layer_program_carries_scope_names(with_weight):
    m, n, p, q, jm = 4, 16, 8, 3, 32
    sds = jax.ShapeDtypeStruct
    prog = engine.layer_program(
        SimulatedBackend(m), sds((m, p, jm), np.float32), sds((m, q, jm), np.float32),
        sds((n, p), np.float32) if with_weight else None,
        mu=1.0, eps_radius=6.0, num_iters=4, trace_every=0,
    )
    hlo = prog.lowering_texts()["hlo"]
    for scope in (profiling.ADMM, profiling.SOLVE, profiling.MIX, profiling.UPDATE,
                  profiling.GRAM, profiling.CHOLESKY):
        assert scope in hlo, scope
    assert (profiling.PROPAGATE in hlo) == with_weight

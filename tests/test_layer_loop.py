"""The layer loop's host side: by default it never waits on the device
between layer programs.  W_l is built by one cached program on the
device, the Cholesky jitter stays there until one fetch after the loop,
and the result is bit for bit the loop that builds W eagerly and
fetches the jitter every layer.

Each check runs on the simulated backend in this process and on a
4-device CPU ``MeshBackend`` in a subprocess (the device count must be
set before JAX starts).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import engine, layerwise, ssfn
from repro.core.backend import make_backend

M, P, Q, JM = 4, 8, 3, 16
POLICY = "gossip:2:1"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def _data():
    kx, kt = jax.random.split(jax.random.PRNGKey(0))
    xw = jax.random.normal(kx, (M, P, JM))
    tw = jax.nn.one_hot(jax.random.randint(kt, (M, JM), 0, Q), Q).transpose(0, 2, 1)
    return xw, tw


def _cfg(**kw):
    return ssfn.SSFNConfig(**{**dict(input_dim=P, num_classes=Q, num_layers=3,
                                     hidden=20, admm_iters=4), **kw})


def _train(backend, cfg, key=jax.random.PRNGKey(1), **kw):
    xw, tw = _data()
    return layerwise.train_decentralized_ssfn(
        xw, tw, cfg, key, backend=backend, trace_every=0, **kw)


def _eager_loop(backend, cfg, key=jax.random.PRNGKey(1)):
    """The loop as it was: W built op by op on the host's schedule and
    each layer's jitter fetched as soon as its program is queued."""
    xw, tw = _data()
    y, t = backend.shard_workers(xw), backend.shard_workers(tw)
    r = ssfn.init_random_matrices(key, cfg)
    o, jitter = [], []
    for layer in range(cfg.num_layers + 1):
        w = None if layer == 0 else ssfn.build_weight(o[-1], r[layer - 1], Q)
        step = engine.fused_layer_step(
            backend, y, t, w, mu=cfg.mu0 if layer == 0 else cfg.mul,
            eps_radius=cfg.eps_radius, num_iters=cfg.admm_iters,
            trace_every=0, donate_y=layer > 1,
        )
        jitter.append(np.asarray(jax.device_get(step.jitter)))
        o.append(step.o_star)
        y = step.y_workers
    return [np.asarray(a) for a in o], np.stack(jitter)


def check_host_syncs(kind, tmp):
    backend = make_backend(kind, M, policy=POLICY)
    layers = _cfg().num_layers + 1
    xw, tw = _data()
    cases = {
        "default": ({}, 1),
        "guard": (dict(guard_divergence=True), 1 + layers),
        "checkpoint": (dict(checkpoint_dir=os.path.join(tmp, "ck")), 1 + layers),
    }
    for name, (kw, want) in cases.items():
        _, log = _train(backend, _cfg(), **kw)
        assert log.host_syncs == want, (name, log.host_syncs, want)
    # The stop reads the objective, so it needs traces; with the guard it
    # shares the guard's one fetch a layer.
    for kw in (dict(), dict(guard_divergence=True)):
        _, log = layerwise.train_decentralized_ssfn(
            xw, tw, _cfg(), jax.random.PRNGKey(1), backend=backend,
            size_estimation_tol=-1.0, **kw)
        assert log.host_syncs == 1 + layers, (kw, log.host_syncs)


def check_matches_eager_loop(kind, tmp):
    backend = make_backend(kind, M, policy=POLICY)
    # hidden > J_m with mu = 1e10 makes every Gram past layer 0 singular
    # in float32, so the guarded Cholesky adds jitter there.
    for cfg, jitters in ((_cfg(), False), (_cfg(hidden=40, mul=1e10), True)):
        params, log = _train(backend, cfg)
        want_o, want_jitter = _eager_loop(backend, cfg)
        assert bool(np.any(want_jitter > 0)) == jitters
        assert log.jitter_levels.dtype == np.int32
        np.testing.assert_array_equal(log.jitter_levels, want_jitter)
        assert len(params.o) == len(want_o)
        for got, want in zip(params.o, want_o):
            np.testing.assert_array_equal(np.asarray(got), want)


def check_deep_train_compiles_nothing(kind, tmp):
    """A 21-layer train after a 3-layer warm-up and a full-depth draw of
    R (the benchmark's set-up) traces and compiles nothing."""
    backend = make_backend(kind, M, policy=POLICY)
    deep = _cfg(num_layers=20)
    key = jax.random.PRNGKey(5)
    params, _ = _train(backend, _cfg(num_layers=2), key=key)
    jax.block_until_ready(params.o)
    jax.block_until_ready(ssfn.init_random_matrices(key, deep))
    key = jax.random.fold_in(key, 1)
    counts = dict.fromkeys(COMPILE_EVENTS, 0)

    def on_event(event, duration, **kwargs):
        if event in counts:
            counts[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        params, log = _train(backend, deep, key=key)
        jax.block_until_ready(params.o)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert counts == dict.fromkeys(COMPILE_EVENTS, 0), counts
    assert len(params.o) == 21 and log.jitter_levels.shape == (21, M)
    assert log.host_syncs == 1


def check_resume_reproduces_run(kind, tmp):
    backend = make_backend(kind, M, policy=POLICY)
    ckpt = os.path.join(tmp, "resume")
    full_params, full_log = _train(backend, _cfg())
    _, first = _train(backend, _cfg(), checkpoint_dir=ckpt, stop_after_layer=1)
    assert first.host_syncs == 2 + 1      # layers 0 and 1 saved, the fetch
    params, log = _train(backend, _cfg(), checkpoint_dir=ckpt, resume=True)
    for got, want in zip(params.o, full_params.o, strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(log.jitter_levels, full_log.jitter_levels)
    assert log.host_syncs == 1 + 2        # layers 2 and 3 saved, the fetch


CHECKS = {f.__name__[len("check_"):]: f for f in (
    check_host_syncs, check_matches_eager_loop,
    check_deep_train_compiles_nothing, check_resume_reproduces_run,
)}


def _on_mesh(check: str, tmp) -> None:
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={M}"
        sys.path.insert(0, {os.path.dirname(__file__)!r})
        import test_layer_loop
        test_layer_loop.CHECKS[{check!r}]("mesh", {str(tmp)!r})
    """)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "PYTHONPATH": src},
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]


@pytest.mark.parametrize("kind", ["simulated", "mesh"])
@pytest.mark.parametrize("check", sorted(CHECKS))
def test_layer_loop(check, kind, tmp_path):
    if kind == "mesh":
        _on_mesh(check, tmp_path)
    else:
        CHECKS[check](kind, str(tmp_path))

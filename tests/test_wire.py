"""Wire-efficient consensus engine: the collective-free hot path
(trace_every), the executable cache under the new knobs (policy x
wire_dtype x trace_every x compress), donation safety when the output
pytree changes, and the facade/launcher plumbing.

Collective-COUNT assertions (lowering stats on a real 8-device mesh)
live in test_multidevice.py — vmap's named-axis collectives trace away,
so only MeshBackend programs contain countable HLO collectives.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dssfn
from repro.core import admm, backend as backend_lib, engine, layerwise, ssfn
from repro.core.backend import SimulatedBackend
from repro.core.policy import ExactMean, Gossip, RingGossip
from repro.core.topology import Ring


def _problem(key, n=16, q=3, j=160, m=4):
    ky, kt = jax.random.split(key)
    y = jax.random.normal(ky, (n, j))
    t = jax.random.normal(kt, (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    return y, t, yw, tw


def _train_problem(key, m=4, p=8, q=3, jm=16):
    cfg = ssfn.SSFNConfig(
        input_dim=p, num_classes=q, num_layers=1, hidden=20, admm_iters=10
    )
    kx, kt, kinit = jax.random.split(key, 3)
    xw = jax.random.normal(kx, (m, p, jm))
    labels = jax.random.randint(kt, (m, jm), 0, q)
    tw = jax.nn.one_hot(labels, q).transpose(0, 2, 1)
    return cfg, xw, tw, kinit


# ------------------------------------------------------------------
# trace_every semantics
# ------------------------------------------------------------------

def test_trace_every_zero_bit_identical_final_iterate():
    """Dropping the trace collectives must not change the solve: the
    final o_star is bit-identical under ExactMean (acceptance)."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(0))
    backend = SimulatedBackend(4)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=30, backend=backend)
    traced = admm.admm_ridge_consensus(yw, tw, trace_every=1, **kw)
    hot = admm.admm_ridge_consensus(yw, tw, trace_every=0, **kw)
    assert jnp.array_equal(traced.o_star, hot.o_star)
    assert jnp.array_equal(traced.o_workers, hot.o_workers)
    assert hot.trace is None
    assert traced.trace is not None


def test_trace_every_zero_bit_identical_under_gossip():
    """...and under an inexact policy, where the gate also removes the
    consensus-error exact_mean + pmax probe (the satellite perf fix)."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(1), m=8)
    pol = RingGossip(rounds=4, degree=2)
    backend = SimulatedBackend(8, policy=pol)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=20, backend=backend)
    traced = admm.admm_ridge_consensus(yw, tw, **kw)
    hot = admm.admm_ridge_consensus(yw, tw, trace_every=0, **kw)
    assert jnp.array_equal(traced.o_star, hot.o_star)
    assert hot.trace is None


def test_trace_every_stride_subsamples_traces():
    _, _, yw, tw = _problem(jax.random.PRNGKey(2))
    backend = SimulatedBackend(4)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=20, backend=backend)
    full = admm.admm_ridge_consensus(yw, tw, **kw)
    strided = admm.admm_ridge_consensus(yw, tw, trace_every=5, **kw)
    assert strided.trace.objective.shape == (4,)
    # Stride-N traces are the every-N-th entries of the full trace.
    assert np.allclose(
        np.asarray(strided.trace.objective),
        np.asarray(full.trace.objective)[4::5],
        rtol=1e-6,
    )
    assert jnp.array_equal(full.o_star, strided.o_star)


def test_trace_every_validation():
    _, _, yw, tw = _problem(jax.random.PRNGKey(3))
    backend = SimulatedBackend(4)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=20, backend=backend)
    with pytest.raises(ValueError, match="divide"):
        admm.admm_ridge_consensus(yw, tw, trace_every=3, **kw)
    with pytest.raises(ValueError, match=">= 0"):
        admm.admm_ridge_consensus(yw, tw, trace_every=-1, **kw)


def test_fused_layer_step_trace_every_zero():
    _, _, yw, tw = _problem(jax.random.PRNGKey(4))
    backend = SimulatedBackend(4)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=10)
    traced = engine.fused_layer_step(backend, yw, tw, None, **kw)
    hot = engine.fused_layer_step(backend, yw, tw, None, trace_every=0, **kw)
    assert hot.trace is None
    assert jnp.array_equal(traced.o_star, hot.o_star)
    assert jnp.array_equal(traced.y_workers, hot.y_workers)


# ------------------------------------------------------------------
# Executable cache under the new knobs
# ------------------------------------------------------------------

def test_distinct_executables_per_wire_knob():
    """(policy, wire_dtype, trace_every, compress) each key a distinct
    lowering; repeats are pure cache hits."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(5), m=8)
    backend = SimulatedBackend(8)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=10, backend=backend)
    runs = [
        dict(policy=Gossip(rounds=2, topology=Ring(2))),
        dict(policy=Gossip(rounds=2, topology=Ring(2), compress=False)),
        dict(policy=Gossip(rounds=2, topology=Ring(2), wire_dtype="bf16")),
        dict(policy=Gossip(rounds=2, topology=Ring(2)), trace_every=0),
        dict(policy=ExactMean()),
        dict(policy=ExactMean(), trace_every=0),
        dict(policy=ExactMean(), trace_every=5),
    ]
    for r in runs:
        admm.admm_ridge_consensus(yw, tw, **kw, **r)
    assert backend.lowerings == len(runs), backend.cache_info()
    hits_before = backend.cache_hits
    for r in runs:
        admm.admm_ridge_consensus(yw, tw, **kw, **r)
    assert backend.lowerings == len(runs), backend.cache_info()
    assert backend.cache_hits == hits_before + len(runs)


def test_fifo_eviction_bound_respected(monkeypatch):
    """The cache never exceeds its bound; evicted entries re-lower."""
    monkeypatch.setattr(backend_lib, "_EXEC_CACHE_SIZE", 3)
    _, _, yw, tw = _problem(jax.random.PRNGKey(6))
    backend = SimulatedBackend(4)
    kw = dict(mu=1e-2, eps_radius=6.0, backend=backend)
    for iters in (2, 4, 6, 8, 10):  # 5 distinct programs > bound of 3
        admm.admm_ridge_consensus(yw, tw, num_iters=iters, **kw)
    assert len(backend._exec_cache) == 3
    assert backend.lowerings == 5
    # Most-recent entries still hit...
    admm.admm_ridge_consensus(yw, tw, num_iters=10, **kw)
    assert backend.lowerings == 5
    # ...the FIFO-evicted first entry re-lowers (correct, just uncached).
    res = admm.admm_ridge_consensus(yw, tw, num_iters=2, **kw)
    assert backend.lowerings == 6
    assert res.o_star.shape == (3, 16)


def test_donation_safe_when_trace_every_changes_output_pytree():
    """trace_every=0 drops the trace leaves from the donated-buffer
    program's outputs; the cache key must separate the two executables
    and both must keep producing correct results in either order."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(7))
    backend = SimulatedBackend(4)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=10)
    w = jax.random.normal(jax.random.PRNGKey(8), (16, 16)) / 4.0

    def run(trace_every):
        # donate_y=True: hand the engine a buffer it may consume.
        y_buf = jnp.array(yw)
        return engine.fused_layer_step(
            backend, y_buf, tw, w, donate_y=True,
            trace_every=trace_every, **kw,
        )

    a = run(1)
    b = run(0)
    c = run(1)
    d = run(0)
    assert b.trace is None and d.trace is None
    assert jnp.array_equal(a.o_star, b.o_star)
    assert jnp.array_equal(a.o_star, c.o_star)
    assert jnp.array_equal(b.o_star, d.o_star)
    assert backend.lowerings == 2, backend.cache_info()


# ------------------------------------------------------------------
# lowering_stats API (collective counts live in test_multidevice)
# ------------------------------------------------------------------

def test_lowering_stats_reports_compiled_program():
    _, _, yw, tw = _problem(jax.random.PRNGKey(9))
    backend = SimulatedBackend(4)
    z0 = jnp.zeros((3, 16))

    def worker(y_m, t_m, z0r):
        a, chol, _ = admm._worker_stats_local(y_m, t_m, 1e-2, False)
        return admm.worker_admm_iterations(
            backend, a, chol, y_m, t_m, z0r,
            mu=1e-2, eps_radius=6.0, num_iters=10, trace_every=0,
        )

    stats = backend.lowering_stats(
        worker, yw, tw, replicated=(z0,), key="stats-probe"
    )
    assert set(stats) == {
        "collective_counts", "collective_wire_bytes", "collective_by_type",
        "flops", "memory_bytes",
    }
    assert stats["flops"] > 0
    assert stats["memory_bytes"] > 0
    # Shares the executable cache with run() — and with lowering_texts,
    # whose StableHLO is what repro.analysis.numerics lints.
    assert ("stats-probe", 2, 1, (), True, None) in backend._exec_cache
    texts = backend.lowering_texts(
        worker, yw, tw, replicated=(z0,), key="stats-probe"
    )
    assert set(texts) == {"stablehlo", "hlo"}
    assert "stablehlo." in texts["stablehlo"]
    assert len(backend._exec_cache) == 1  # same entry, no new executable

    info = backend.cache_info()
    from repro.analysis import check_cache_info_schema

    assert not check_cache_info_schema(info, subject="backend")
    assert info["entries"] == len(info["keys"]) == 1


# ------------------------------------------------------------------
# Facade / layerwise plumbing
# ------------------------------------------------------------------

def test_layerwise_trace_every_zero_log_is_empty_but_trains():
    cfg, xw, tw, kinit = _train_problem(jax.random.PRNGKey(10))
    backend = SimulatedBackend(4)
    p_hot, log_hot = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=backend, trace_every=0
    )
    p_tr, log_tr = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=backend, trace_every=1
    )
    for a, b in zip(p_hot.o, p_tr.o):
        assert jnp.array_equal(a, b)
    assert log_hot.layer_costs == []
    assert log_hot.admm_objective.shape == (cfg.num_layers + 1, 0)
    assert log_hot.comm_scalars == log_tr.comm_scalars
    assert len(log_tr.layer_costs) == cfg.num_layers + 1


def test_layerwise_trace_every_zero_rejects_size_estimation():
    cfg, xw, tw, kinit = _train_problem(jax.random.PRNGKey(11))
    with pytest.raises(ValueError, match="size_estimation"):
        layerwise.train_decentralized_ssfn(
            xw, tw, cfg, kinit, backend=SimulatedBackend(4),
            trace_every=0, size_estimation_tol=1e-3,
        )


def test_trainspec_wire_dtype_and_trace_every():
    cfg, xw, tw, kinit = _train_problem(jax.random.PRNGKey(12))
    spec = dssfn.TrainSpec(
        cfg=cfg, workers=4, policy="gossip:3",
        wire_dtype="bf16", trace_every=0,
    )
    pol = spec.resolve_policy()
    assert pol == Gossip(rounds=3, topology=Ring(1), wire_dtype="bfloat16")
    assert pol.wire_bits == 16
    result = dssfn.train(spec, xw, tw, kinit)
    assert result.log.layer_costs == []
    acc = dssfn.evaluate(
        result,
        jax.random.normal(jax.random.PRNGKey(13), (cfg.input_dim, 12)),
        jnp.zeros((12,), jnp.int32),
    )
    assert 0.0 <= acc <= 1.0


def test_trainspec_wire_dtype_rejects_nonwire_policies():
    cfg, *_ = _train_problem(jax.random.PRNGKey(14))
    with pytest.raises(ValueError, match="wire_dtype"):
        dssfn.TrainSpec(
            cfg=cfg, workers=4, policy=ExactMean(), wire_dtype="bf16"
        ).resolve_policy()
    with pytest.raises(ValueError, match="wire_dtype"):
        dssfn.TrainSpec(
            cfg=cfg, workers=4, policy="quantized:4", wire_dtype="bf16"
        ).resolve_policy()


def test_launcher_flags_build_wire_policy():
    from repro.launch.train_dssfn import build_policy, parse_args

    args = parse_args(
        ["--consensus", "gossip:4:2", "--wire-dtype", "bf16",
         "--trace-every", "0"]
    )
    pol = build_policy(args)
    assert pol == RingGossip(rounds=4, degree=2)  # wire applied via spec
    spec_pol = dssfn.TrainSpec(
        cfg=ssfn.SSFNConfig(input_dim=4, num_classes=2, num_layers=1,
                            hidden=8),
        workers=4, policy=pol, wire_dtype=args.wire_dtype,
    ).resolve_policy()
    assert spec_pol.wire_dtype == "bfloat16"
    assert args.trace_every == 0
    serial = build_policy(
        parse_args(["--consensus", "gossip:4:2", "--no-compress"])
    )
    assert serial == RingGossip(rounds=4, degree=2, compress=False)
    assert dataclasses.replace(serial, compress=True) == pol

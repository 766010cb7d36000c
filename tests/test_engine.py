"""Compile-once layer engine: executable caching, compile-count regression,
fused layer-step semantics, and Pallas kernel-path parity.

Single-device portion; the M=8 host-mesh engine runs live in
test_multidevice.py (XLA_FLAGS must be set before jax initializes).
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core import admm, engine, layerwise, ssfn
from repro.core.backend import MeshBackend, SimulatedBackend
from repro.core.policy import ExactMean, RingGossip


def _problem(key, n, q, j, m):
    ky, kt = jax.random.split(key)
    y = jax.random.normal(ky, (n, j))
    t = jax.random.normal(kt, (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    return y, t, yw, tw


def _train_problem(key, m, p, q, jm, num_layers, hidden, admm_iters, **cfg_kw):
    cfg = ssfn.SSFNConfig(
        input_dim=p, num_classes=q, num_layers=num_layers, hidden=hidden,
        admm_iters=admm_iters, **cfg_kw,
    )
    kx, kt, kinit = jax.random.split(key, 3)
    xw = jax.random.normal(kx, (m, p, jm))
    labels = jax.random.randint(kt, (m, jm), 0, q)
    tw = jax.nn.one_hot(labels, q).transpose(0, 2, 1)
    return cfg, xw, tw, kinit


# ------------------------------------------------------------------
# Executable cache: compile counts
# ------------------------------------------------------------------

def test_repeated_admm_solves_compile_once():
    """Same shapes + hyper-parameters through one backend: ONE lowering."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(0), 16, 3, 160, 4)
    backend = SimulatedBackend(4)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=20, backend=backend)
    a = admm.admm_ridge_consensus(yw, tw, **kw)
    b = admm.admm_ridge_consensus(yw, tw, **kw)
    assert backend.lowerings == 1, backend.cache_info()
    assert backend.cache_hits == 1
    assert jnp.allclose(a.o_star, b.o_star)


def test_admm_new_hyperparams_retrace():
    """mu is part of the cache key — changing it must re-lower."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(1), 16, 3, 160, 4)
    backend = SimulatedBackend(4)
    admm.admm_ridge_consensus(yw, tw, mu=1e-2, eps_radius=6.0, num_iters=10,
                              backend=backend)
    admm.admm_ridge_consensus(yw, tw, mu=1e-1, eps_radius=6.0, num_iters=10,
                              backend=backend)
    assert backend.lowerings == 2, backend.cache_info()


@pytest.mark.parametrize("kind", ["simulated", "mesh"])
def test_train_lowers_once_per_distinct_layer_shape(kind):
    """The compile-count regression test: an L-layer train lowers each
    DISTINCT layer program exactly once, not once per layer solve.

    With L=3 there are 4 layer solves but only 3 distinct programs:
    l=0 (no W, P-dim features, caller-owned Y), l=1 (W: n x P, Y still
    caller-reachable so no donation) and l=2..3 (W: n x n, engine-owned
    Y donated — shared executable)."""
    if kind == "mesh":
        from repro.launch.mesh import make_worker_mesh

        backend = MeshBackend(make_worker_mesh(1))
    else:
        backend = SimulatedBackend(1)
    cfg, xw, tw, kinit = _train_problem(
        jax.random.PRNGKey(2), m=1, p=8, q=3, jm=24, num_layers=3, hidden=20,
        admm_iters=10,
    )
    params, _ = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=backend
    )
    assert len(params.o) == 4                      # L+1 layer solves ran
    assert backend.lowerings == 3, backend.cache_info()
    # l=3 hits l=2's cached executable and runs it straight (same W
    # shape, same donation) — the 4th solve costs zero lowerings.
    assert backend.cache_hits == 1, backend.cache_info()


def test_second_train_is_fully_cached():
    """A second identical train through the same backend lowers NOTHING."""
    backend = SimulatedBackend(2)
    cfg, xw, tw, kinit = _train_problem(
        jax.random.PRNGKey(3), m=2, p=8, q=3, jm=16, num_layers=2, hidden=20,
        admm_iters=10,
    )
    layerwise.train_decentralized_ssfn(xw, tw, cfg, kinit, backend=backend)
    lowerings_after_first = backend.lowerings
    layerwise.train_decentralized_ssfn(xw, tw, cfg, kinit, backend=backend)
    assert backend.lowerings == lowerings_after_first, backend.cache_info()


# ------------------------------------------------------------------
# Fused layer step semantics
# ------------------------------------------------------------------

def test_fused_layer_step_matches_separate_propagate_and_solve():
    """One fused program == propagate (map_workers) then admm solve."""
    m, p, q, jm, n = 4, 8, 3, 16, 20
    _, _, yw, tw = _problem(jax.random.PRNGKey(4), p, q, m * jm, m)
    w = jax.random.normal(jax.random.PRNGKey(5), (n, p)) / jnp.sqrt(p)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=25)

    backend = SimulatedBackend(m)
    step = engine.fused_layer_step(backend, yw, tw, w, **kw)

    y_prop = jax.vmap(lambda ym: jax.nn.relu(w @ ym))(yw)
    ref = admm.admm_ridge_consensus(y_prop, tw, backend=SimulatedBackend(m), **kw)
    assert jnp.allclose(step.y_workers, y_prop, atol=1e-6)
    assert jnp.allclose(step.o_star, ref.o_star, atol=1e-6)
    assert jnp.allclose(step.trace.objective, ref.trace.objective, atol=1e-4)


def test_fused_layer_step_no_weight_matches_plain_solve():
    """l=0 (w=None): the fused step IS the plain layer solve + identity Y."""
    m = 4
    _, _, yw, tw = _problem(jax.random.PRNGKey(6), 16, 3, 160, m)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=25)
    step = engine.fused_layer_step(SimulatedBackend(m), yw, tw, None, **kw)
    ref = admm.admm_ridge_consensus(yw, tw, backend=SimulatedBackend(m), **kw)
    assert jnp.allclose(step.y_workers, yw)
    assert jnp.allclose(step.o_star, ref.o_star, atol=1e-6)


def test_fused_layer_step_worker_count_mismatch():
    _, _, yw, tw = _problem(jax.random.PRNGKey(7), 16, 3, 160, 4)
    with pytest.raises(ValueError, match="worker shards"):
        engine.fused_layer_step(
            SimulatedBackend(8), yw, tw, None,
            mu=1e-2, eps_radius=6.0, num_iters=5,
        )


# ------------------------------------------------------------------
# Backend run() API: replicated operands + donation validation
# ------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["simulated", "mesh"])
def test_replicated_operands_are_operands_not_constants(kind):
    """The same cached executable must serve DIFFERENT replicated values —
    the property that makes weight-passing safe under the cache."""
    if kind == "mesh":
        from repro.launch.mesh import make_worker_mesh

        backend = MeshBackend(make_worker_mesh(1))
        m = 1
    else:
        backend = SimulatedBackend(4)
        m = 4
    x = jnp.arange(m * 6, dtype=jnp.float32).reshape(m, 6)

    def worker(x_m, shift):
        return x_m + shift

    key = ("shift-test",)
    out1 = backend.run(worker, x, replicated=(jnp.float32(1.0),), key=key)
    out2 = backend.run(worker, x, replicated=(jnp.float32(5.0),), key=key)
    assert backend.lowerings == 1, backend.cache_info()
    assert jnp.allclose(out2 - out1, 4.0)


def test_identity_keyed_cache_skips_array_closures():
    """A key=None fn that closes over an array keeps per-call semantics:
    rebinding the captured array (same fn object, nonlocal cell update)
    must NOT return stale cached results."""
    backend = SimulatedBackend(2)
    x = jnp.ones((2, 3))

    def make_fn():
        w = jnp.float32(1.0)

        def f(x_m):
            return x_m * w

        def set_w(v):
            nonlocal w
            w = v

        return f, set_w

    fn, set_w = make_fn()
    assert jnp.allclose(backend.run(fn, x), 1.0)
    set_w(jnp.float32(5.0))
    assert jnp.allclose(backend.run(fn, x), 5.0)   # not the stale 1.0
    # Array-closure fns are never identity-cached at all.
    assert backend.cache_info()["entries"] == 0


def test_donate_index_validation():
    backend = SimulatedBackend(2)
    x = jnp.zeros((2, 3))
    with pytest.raises(ValueError, match="donate"):
        backend.run(lambda a: a, x, donate=(1,))


# ------------------------------------------------------------------
# Pallas kernel-path parity (128-aligned shapes; interpret mode on CPU)
# ------------------------------------------------------------------

@pytest.mark.parametrize("policy", [
    ExactMean(),
    RingGossip(rounds=4, degree=1),
], ids=["exact", "gossip"])
def test_use_kernels_training_parity_simulated(policy):
    """use_kernels=True == einsum path through the whole layer engine
    (fused propagate_gram + gram + matmul_relu vs plain jnp)."""
    m = 4
    cfg, xw, tw, kinit = _train_problem(
        jax.random.PRNGKey(8), m=m, p=128, q=3, jm=128, num_layers=2,
        hidden=128, admm_iters=15,
    )
    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    p_ref, _ = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=SimulatedBackend(m, policy=policy)
    )
    p_k, _ = layerwise.train_decentralized_ssfn(
        xw, tw, cfg_k, kinit, backend=SimulatedBackend(m, policy=policy)
    )
    for a, b in zip(p_ref.o, p_k.o):
        rel = float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(a), 1e-30))
        assert rel < 1e-6, rel


def test_use_kernels_training_parity_mesh_single_device():
    """Kernel-path parity through MeshBackend (shard_map + Pallas)."""
    from repro.launch.mesh import make_worker_mesh

    cfg, xw, tw, kinit = _train_problem(
        jax.random.PRNGKey(9), m=1, p=128, q=3, jm=128, num_layers=2,
        hidden=128, admm_iters=15,
    )
    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    p_ref, _ = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=MeshBackend(make_worker_mesh(1))
    )
    p_k, _ = layerwise.train_decentralized_ssfn(
        xw, tw, cfg_k, kinit, backend=MeshBackend(make_worker_mesh(1))
    )
    for a, b in zip(p_ref.o, p_k.o):
        rel = float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(a), 1e-30))
        assert rel < 1e-6, rel


def test_use_kernels_misaligned_shapes_fall_back():
    """Odd shapes route every op to the einsum path — results identical to
    use_kernels=False, no assertion failures from the kernels."""
    m = 2
    cfg, xw, tw, kinit = _train_problem(
        jax.random.PRNGKey(10), m=m, p=9, q=3, jm=20, num_layers=1, hidden=22,
        admm_iters=10,
    )
    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    p_ref, _ = layerwise.train_decentralized_ssfn(xw, tw, cfg, kinit)
    p_k, _ = layerwise.train_decentralized_ssfn(xw, tw, cfg_k, kinit)
    for a, b in zip(p_ref.o, p_k.o):
        assert jnp.allclose(a, b, atol=1e-6)


# ------------------------------------------------------------------
# Device-resident traces / size estimation through the engine
# ------------------------------------------------------------------

def test_engine_log_matches_plain_objective():
    """Engine traces (device-accumulated, fetched once) are right: each
    layer's final traced objective equals sum_m ||T_m - O_l Y_{l,m}||^2
    recomputed in plain float64 from the returned params, with Y
    propagated by plain relu(W @ Y)."""
    import numpy as np

    m = 4
    cfg, xw, tw, kinit = _train_problem(
        jax.random.PRNGKey(11), m=m, p=8, q=3, jm=16, num_layers=1, hidden=20,
        admm_iters=20,
    )
    params, log = layerwise.train_decentralized_ssfn(xw, tw, cfg, kinit)
    layers = cfg.num_layers + 1
    for trace in (log.admm_objective, log.admm_primal, log.admm_dual,
                  log.consensus_error):
        assert trace.shape == (layers, cfg.admm_iters)
    assert len(log.layer_costs) == layers

    t = np.asarray(tw, np.float64)
    y = np.asarray(xw, np.float64)
    for layer in range(layers):
        if layer > 0:
            w = np.asarray(ssfn.build_weight(
                params.o[layer - 1], params.r[layer - 1], cfg.num_classes
            ), np.float64)
            y = np.maximum(np.einsum("ij,mjk->mik", w, y), 0.0)
        o = np.asarray(params.o[layer], np.float64)
        want = float(np.sum((t - np.einsum("qi,mik->mqk", o, y)) ** 2))
        np.testing.assert_allclose(log.admm_objective[layer, -1], want, rtol=1e-5)
        assert log.layer_costs[layer] == log.admm_objective[layer, -1]


def test_standalone_solve_shares_the_layer_zero_program():
    """admm_ridge_consensus runs the engine's l=0 program: after a
    fused_layer_step(w=None) with the same hyper-parameters and shapes it
    adds no lowering and returns the bit-identical readout."""
    m = 4
    _, _, yw, tw = _problem(jax.random.PRNGKey(13), 16, 3, 160, m)
    backend = SimulatedBackend(m)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=20)
    step = engine.fused_layer_step(backend, yw, tw, None, **kw)
    assert backend.lowerings == 1, backend.cache_info()
    res = admm.admm_ridge_consensus(yw, tw, backend=backend, **kw)
    assert backend.lowerings == 1, backend.cache_info()
    assert backend.cache_hits == 1
    assert jnp.array_equal(res.o_star, step.o_star)
    assert jnp.array_equal(res.o_workers, step.o_workers)
    assert jnp.array_equal(res.jitter, step.jitter)


def test_size_estimation_through_engine():
    backend = SimulatedBackend(4)
    cfg, xw, tw, kinit = _train_problem(
        jax.random.PRNGKey(12), m=4, p=8, q=3, jm=16, num_layers=4, hidden=20,
        admm_iters=20,
    )
    params, log = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=backend, size_estimation_tol=0.5
    )
    depth = len(params.o) - 1
    assert depth < cfg.num_layers
    assert len(params.r) == depth
    assert len(log.layer_costs) == depth + 1
    assert log.admm_objective.shape == (depth + 1, cfg.admm_iters)

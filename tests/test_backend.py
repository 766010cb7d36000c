"""ConsensusBackend seam: SimulatedBackend/MeshBackend equivalence and the
factory/validation error paths.

Single-device portion of the backend test matrix; the M=8 host-mesh
parity runs out-of-process in test_multidevice.py (XLA_FLAGS must be set
before jax initializes).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import admm, consensus, layerwise, ssfn, topology
from repro.core.backend import (
    MeshBackend,
    SimulatedBackend,
    make_backend,
)
from repro.core.policy import RingGossip


def _problem(key, n, q, j, m):
    ky, kt = jax.random.split(key)
    y = jax.random.normal(ky, (n, j))
    t = jax.random.normal(kt, (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    return y, t, yw, tw


# ------------------------------------------------------------------
# SimulatedBackend == the pre-backend batched semantics
# ------------------------------------------------------------------

def test_simulated_exact_matches_oracle():
    y, t, yw, tw = _problem(jax.random.PRNGKey(0), 24, 4, 240, 6)
    eps = 8.0
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=eps)
    res = admm.admm_ridge_consensus(
        yw, tw, mu=1e-2, eps_radius=eps, num_iters=300,
        backend=SimulatedBackend(6),
    )
    rel = float(jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle))
    assert rel < 1e-4, rel


def test_default_backend_is_simulated_exact():
    """admm_ridge_consensus with no backend == explicit SimulatedBackend."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(1), 16, 3, 160, 4)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=50)
    a = admm.admm_ridge_consensus(yw, tw, **kw)
    b = admm.admm_ridge_consensus(yw, tw, backend=SimulatedBackend(4), **kw)
    assert jnp.allclose(a.o_star, b.o_star)
    assert jnp.allclose(a.trace.objective, b.trace.objective)


def test_ring_gossip_consensus_matches_dense_h():
    """One vmapped ring-gossip consensus call == dense doubly-stochastic
    circular H — the primitive the gossip backend is built on."""
    m, degree, rounds = 8, 2, 5
    x = jax.random.normal(jax.random.PRNGKey(2), (m, 4, 6))
    h = topology.circular_mixing_matrix(m, degree)
    want = consensus.gossip_average(x, h, rounds)
    backend = SimulatedBackend(m, policy=RingGossip(rounds=rounds, degree=degree))
    got = backend.run(backend.consensus_mean, x)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_gossip_backend_converges_to_oracle():
    y, t, yw, tw = _problem(jax.random.PRNGKey(3), 16, 3, 160, 8)
    eps = 6.0
    h = topology.circular_mixing_matrix(8, 2)
    rounds = topology.gossip_rounds_for_tolerance(h, 1e-9)
    backend = SimulatedBackend(8, policy=RingGossip(rounds=rounds, degree=2))
    res = admm.admm_ridge_consensus(
        yw, tw, mu=1e-2, eps_radius=eps, num_iters=200, backend=backend
    )
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=eps)
    rel = float(jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle))
    assert rel < 1e-3, rel


def test_backend_trace_shapes_and_feasibility():
    _, _, yw, tw = _problem(jax.random.PRNGKey(4), 16, 3, 160, 4)
    eps = 0.5  # tight ball: projection active
    res = admm.admm_ridge_consensus(
        yw, tw, mu=1e-1, eps_radius=eps, num_iters=30, backend=SimulatedBackend(4)
    )
    assert res.o_star.shape == (3, 16)
    assert res.o_workers.shape == (4, 3, 16)
    assert res.lam.shape == (4, 3, 16)
    assert res.trace.objective.shape == (30,)
    assert float(jnp.linalg.norm(res.o_star)) <= eps * (1 + 1e-5)


# ------------------------------------------------------------------
# MeshBackend on the degenerate 1-device mesh (full mesh runs live in
# test_multidevice.py)
# ------------------------------------------------------------------

def test_mesh_backend_single_device():
    from repro.launch.mesh import make_worker_mesh

    y, t, yw, tw = _problem(jax.random.PRNGKey(5), 16, 3, 64, 1)
    backend = MeshBackend(make_worker_mesh(1))
    assert backend.num_workers == 1
    res = admm.admm_ridge_consensus(
        yw, tw, mu=1e-2, eps_radius=6.0, num_iters=200, backend=backend
    )
    cen = admm.centralized_ridge_admm(y, t, mu=1e-2, eps_radius=6.0, num_iters=200)
    rel = float(jnp.linalg.norm(res.o_star - cen.o_star) / jnp.linalg.norm(cen.o_star))
    assert rel < 1e-5, rel


def test_layerwise_training_accepts_backend():
    m = 4
    cfg = ssfn.SSFNConfig(
        input_dim=8, num_classes=3, num_layers=1, hidden=20, admm_iters=30
    )
    kx, kt, kinit = jax.random.split(jax.random.PRNGKey(6), 3)
    xw = jax.random.normal(kx, (m, 8, 16))
    labels = jax.random.randint(kt, (m, 16), 0, 3)
    tw = jax.nn.one_hot(labels, 3).transpose(0, 2, 1)
    p_default, log_default = layerwise.train_decentralized_ssfn(xw, tw, cfg, kinit)
    p_backend, log_backend = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=SimulatedBackend(m)
    )
    for a, b in zip(p_default.o, p_backend.o):
        assert jnp.allclose(a, b, atol=1e-6)
    assert log_default.comm_scalars == log_backend.comm_scalars


def test_layerwise_gossip_backend_comm_accounting():
    m = 4
    cfg = ssfn.SSFNConfig(
        input_dim=8, num_classes=3, num_layers=1, hidden=20, admm_iters=10
    )
    kx, kt, kinit = jax.random.split(jax.random.PRNGKey(7), 3)
    xw = jax.random.normal(kx, (m, 8, 16))
    labels = jax.random.randint(kt, (m, 16), 0, 3)
    tw = jax.nn.one_hot(labels, 3).transpose(0, 2, 1)
    backend = SimulatedBackend(m, policy=RingGossip(rounds=3, degree=1))
    _, log = layerwise.train_decentralized_ssfn(xw, tw, cfg, kinit, backend=backend)
    # eq. 15 with B = 2*degree*rounds exchanges per consensus.
    assert backend.exchanges_per_consensus() == 6
    expected = 3 * (8 + 20) * 6 * 10  # Q*(n_0 + n_1)*B*K over the two layers
    assert log.comm_scalars == expected


# ------------------------------------------------------------------
# Error paths
# ------------------------------------------------------------------

def test_make_backend_error_paths():
    with pytest.raises(ValueError, match="unknown backend kind"):
        make_backend("tpu-pod")
    with pytest.raises(ValueError, match="num_workers"):
        make_backend("simulated")


def test_backend_validation():
    # The PR-3 mode= aliases are gone: a clean TypeError that names the
    # rejected keyword and points at the policy= migration path.
    with pytest.raises(TypeError, match="mode.*removed.*parse_policy"):
        SimulatedBackend(4, mode="psum")
    with pytest.raises(TypeError, match="degree, mode"):
        SimulatedBackend(4, mode="gossip", degree=0)
    with pytest.raises(TypeError, match="num_rounds"):
        SimulatedBackend(4, num_rounds=0)
    with pytest.raises(ValueError, match="num_workers"):
        SimulatedBackend(0)
    with pytest.raises(TypeError, match="policy must be a ConsensusPolicy"):
        SimulatedBackend(4, policy="gossip:2")  # spec strings: make_backend


def test_mismatched_worker_count_rejected():
    _, _, yw, tw = _problem(jax.random.PRNGKey(8), 16, 3, 160, 4)
    with pytest.raises(ValueError, match="worker shards"):
        admm.admm_ridge_consensus(
            yw, tw, mu=1e-2, eps_radius=6.0, num_iters=5,
            backend=SimulatedBackend(8),
        )


def test_mesh_backend_requires_worker_axis():
    from repro.launch.mesh import make_host_mesh

    with pytest.raises(ValueError, match="workers"):
        MeshBackend(make_host_mesh(1))
